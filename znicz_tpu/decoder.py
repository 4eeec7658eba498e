"""Decoder-stack units: a token embedding, a decoder layer (pre-norm
attention and a dense or sparse-expert feed-forward, both residual) and
the head (final RMSNorm and untied logits), built from the keys of a
model's public configuration — data, not code: a second decoder is a
second dictionary (``samples/laguna.py`` holds the first).

They follow the framework's unit contract (pure ``apply(params, x)``,
``params()`` a dict of ``Array``s, a GD twin, registry types
``token_embedding`` / ``decoder_layer`` / ``lm_head`` for
``StandardWorkflow``) with four things the older units do not have, all
of which the fused trainer OBSERVES rather than is told:

  - a unit with many tensors: ``DecoderLayer.params()`` holds its dozen,
    ``decay_exempt`` names those weight decay skips (norms, the attention
    gate, the router);
  - a unit that counts: ``apply_counted`` returns ``(y, counters)``, small
    int32 arrays that leave the device with the step's loss, in the same
    pull (``FusedTrainer.loss_and_metrics`` / ``_book_counted``);
  - a unit that notes how it was traced: ``run_stats(units)`` turns what
    the layers of a run noted on the host (here which way each attention
    core runs, ``ops.attention.core_tiles``, and how often the process
    traced and lowered the core's kernels) into ``FusedTrainer.stats``
    when the run ends — no device work, nothing in a step;
  - a unit that asks for rematerialisation (``remat = True``): training
    keeps a decoder layer's input only, 67 MB a layer at 16,384 tokens of
    width 2,048, and recomputes the rest on the way back.

Weights are made ON THE DEVICE from the unit's seeded stream
(``init_params``; the same seed gives the same tensors), so 0.7 billion
parameters never cross the host link; ``init_params`` may be called again
to get the seeded weights back.

``CharEmbedding`` (``attention.py``) is untouched: its ids stay ``uint8``
with a learned position table; ``TokenEmbedding`` takes ``int32`` ids and
adds no positions (the layers rotate them in).
"""

from __future__ import annotations

from typing import Dict

import numpy as np

from znicz_tpu.core import prng
from znicz_tpu.memory import Array
from znicz_tpu.nn_units import ForwardBase
from znicz_tpu.ops import moe
from znicz_tpu.ops.attention import (apply_rope, blocked_attention,
                                     core_tiles, kernel_counts, rope_tables)


def rms_norm(x, gain, eps: float):
    """``x / sqrt(mean(x^2) + eps) * gain`` over the last axis, in
    float32; the result in ``x``'s dtype."""
    import jax
    import jax.numpy as jnp

    x32 = x.astype(jnp.float32)
    scale = jax.lax.rsqrt(jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
                          + eps)
    return (x32 * scale * gain.astype(jnp.float32)).astype(x.dtype)


class _DeviceInitialised(ForwardBase):
    """A forward unit whose tensors are listed by ``param_shapes()`` as
    ``key -> (shape, stddev)`` (stddev ``None``: ones, a norm's gain) and
    made on the device."""

    #: keys of ``params()`` that weight decay skips
    decay_exempt = ()

    def __init__(self, workflow=None, name=None, init_std=0.02, **kwargs):
        super().__init__(workflow=workflow, name=name, **kwargs)
        self.init_std = float(init_std)
        self.tensors: Dict[str, Array] = {}

    def param_shapes(self) -> Dict[str, tuple]:
        raise NotImplementedError

    def params(self) -> Dict[str, Array]:
        return dict(self.tensors)

    def init_params(self) -> Dict[str, object]:
        """The seeded tensors, fresh, as device arrays: normal(0, stddev)
        from key ``i`` of the unit's stream for the ``i``-th tensor."""
        import jax
        import jax.numpy as jnp

        shapes = self.param_shapes()

        def make(base):                 # one program a unit, not a tensor
            return {key: (jnp.ones(shape, jnp.float32) if std is None else
                          jax.random.normal(jax.random.fold_in(base, i),
                                            shape, jnp.float32) * std)
                    for i, (key, (shape, std)) in enumerate(shapes.items())}

        return jax.jit(make)(prng.get(self.name).jax_base_key())

    def initialize(self, device=None, **kwargs):
        if not self.tensors:
            self.tensors = {key: Array() for key in self.param_shapes()}
        if not all(self.tensors.values()):      # not restored before
            for key, value in self.init_params().items():
                self.tensors[key].devmem = value
        self.create_output()
        for arr in self.tensors.values():
            arr.initialize(device)
        super().initialize(device=device, **kwargs)


class TokenEmbedding(_DeviceInitialised):
    """``(batch, seq)`` integer ids -> ``(batch, seq, hidden)``.  The ids
    are ``int32`` end to end (the loader's array, the resident twin, the
    gather; no cast touches an integer on the way); ids that arrive as
    floats (the unit engine's float32 minibatch) are cast back."""

    def __init__(self, workflow=None, name=None, vocab=256, hidden=64,
                 **kwargs):
        super().__init__(workflow=workflow, name=name, **kwargs)
        self.vocab, self.hidden = int(vocab), int(hidden)

    def param_shapes(self):
        return {"embed": ((self.vocab, self.hidden), self.init_std)}

    def output_shape_for(self, in_shape):
        return (in_shape[0], in_shape[1], self.hidden)

    def apply(self, params, x):
        import jax.numpy as jnp

        return jnp.take(params["embed"], x.astype(jnp.int32), axis=0,
                        mode="clip")


class DecoderLayer(_DeviceInitialised):
    """One pre-norm decoder layer::

        h = x + Attn(RMSNorm(x)),    y = h + FFN(RMSNorm(h))

    ``Attn``: grouped-query (``heads`` query heads read ``kv_heads``),
    no biases, rotary positions on the first ``rotary_dim`` dimensions of
    every head (``rope``: ``theta`` and optionally the YaRN numbers —
    ``ops.attention.rope_tables``), causal and, with ``window``, limited
    to the last ``window`` keys (``ops.attention.blocked_attention``);
    with ``gating`` head ``h``'s output is multiplied by ``sigmoid(x^ .
    w_gate[:, h])``, ``x^`` the normed input.

    ``FFN``: ``dense_width`` set — a SwiGLU of that width; else the expert
    layer: ``experts_total`` routed experts of ``expert_width`` with
    ``experts_per_token`` a token (``ops.moe.route``: sigmoid scores,
    weights ``routed_scale * s / sum of the chosen``) plus one shared
    expert of ``shared_width``.  The layer is TOLD what it holds —
    ``experts_held`` experts from ``first_expert`` — routes over all
    ``experts_total``, and adds the shared expert and its own experts'
    part (``ops.moe.held_experts``); what the experts that live on other
    chips would add is left out, here and in the reference alike."""

    remat = True
    decay_exempt = ("norm_attn", "norm_ffn", "w_gate", "router")

    def __init__(self, workflow=None, name=None, heads=4, kv_heads=2,
                 head_dim=16, window=None, rope=None, gating=False,
                 dense_width=0, expert_width=0, shared_width=0,
                 experts_total=0, experts_held=0, first_expert=0,
                 experts_per_token=0, routed_scale=1.0, norm_eps=1e-6,
                 **kwargs):
        super().__init__(workflow=workflow, name=name, **kwargs)
        self.heads, self.kv_heads = int(heads), int(kv_heads)
        self.head_dim = int(head_dim)
        self.window = int(window) if window else None
        self.rope = dict(rope or {"theta": 10000.0})
        self.gating = bool(gating)
        self.dense_width = int(dense_width)
        self.expert_width = int(expert_width)
        self.shared_width = int(shared_width)
        self.experts_total = int(experts_total)
        self.experts_held = int(experts_held)
        self.first_expert = int(first_expert)
        self.experts_per_token = int(experts_per_token)
        self.routed_scale = float(routed_scale)
        self.norm_eps = float(norm_eps)
        if self.heads % self.kv_heads:
            raise ValueError(f"{self.name}: {self.heads} query heads do "
                             f"not divide over {self.kv_heads} KV heads")
        if not self.dense_width and not (
                0 < self.experts_held <= self.experts_total
                and 0 <= self.first_expert
                <= self.experts_total - self.experts_held
                and 0 < self.experts_per_token <= self.experts_total):
            raise ValueError(
                f"{self.name}: experts {self.first_expert}.."
                f"{self.first_expert + self.experts_held} of "
                f"{self.experts_total}, {self.experts_per_token} a token")
        self.hidden = 0             # the input's width, at initialize
        self.core_in_kernels = None     # noted when ``apply_counted`` traces

    @property
    def sparse(self) -> bool:
        return not self.dense_width

    def output_shape_for(self, in_shape):
        return tuple(in_shape)

    def param_shapes(self):
        d, std = self.hidden, self.init_std
        q, kv = self.heads * self.head_dim, self.kv_heads * self.head_dim
        shapes = {"norm_attn": ((d,), None), "wq": ((d, q), std),
                  "wk": ((d, kv), std), "wv": ((d, kv), std)}
        if self.gating:
            shapes["w_gate"] = ((d, self.heads), std)
        shapes.update({"wo": ((q, d), std), "norm_ffn": ((d,), None)})
        if self.sparse:
            held, f, s = self.experts_held, self.expert_width, \
                self.shared_width
            shapes.update({
                "router": ((d, self.experts_total), std),
                "shared_gate": ((d, s), std), "shared_up": ((d, s), std),
                "shared_down": ((s, d), std),
                "experts_gate": ((held, d, f), std),
                "experts_up": ((held, d, f), std),
                "experts_down": ((held, f, d), std)})
        else:
            w = self.dense_width
            shapes.update({"ffn_gate": ((d, w), std), "ffn_up": ((d, w), std),
                           "ffn_down": ((w, d), std)})
        return shapes

    def initialize(self, device=None, **kwargs):
        self.hidden = int(self.input.shape[-1])
        super().initialize(device=device, **kwargs)

    # -- counters --------------------------------------------------------------

    @staticmethod
    def book_counters(stats: dict, layers: list) -> tuple:
        """The expert layers' counts of the steps just pulled, one dict a
        layer (``rows_by_expert`` ``(steps, held)`` or ``(held,)``,
        ``rows_dropped``), into ``FusedTrainer.stats``; returns the names
        written.  ``moe_rows_by_expert`` is the mean over the counted
        steps (train and validation alike) of a step's busiest, mean and
        idlest held expert, the layers' extremes."""
        rows = [np.asarray(c["rows_by_expert"], np.int64) for c in layers]
        rows = [r.reshape(-1, r.shape[-1]) for r in rows]
        if not rows:
            return ()
        steps, done = rows[0].shape[0], int(stats.get("moe_counted_steps", 0))
        new = {"max": np.max([r.max(axis=1) for r in rows], axis=0),
               "mean": np.mean([r.mean(axis=1) for r in rows], axis=0),
               "min": np.min([r.min(axis=1) for r in rows], axis=0)}
        old = stats.get("moe_rows_by_expert") or dict.fromkeys(new, 0.0)
        stats["moe_rows_by_expert"] = {
            k: (old[k] * done + float(v.sum())) / (done + steps)
            for k, v in new.items()}
        stats["moe_rows_routed"] = int(stats.get("moe_rows_routed", 0)
                                       + sum(r.sum() for r in rows))
        stats["moe_rows_dropped"] = int(
            stats.get("moe_rows_dropped", 0)
            + sum(np.sum(c["rows_dropped"], dtype=np.int64) for c in layers))
        stats["moe_counted_steps"] = done + steps
        return ("moe_rows_by_expert", "moe_rows_routed", "moe_rows_dropped",
                "moe_counted_steps")

    @staticmethod
    def run_stats(layers: list) -> dict:
        """What the decoder layers of a run noted while they were traced:
        how many run their attention core in the Pallas kernels and how
        many composed of XLA operations (``core_in_kernels``, set by the
        last trace of ``apply_counted``), and the process's count of
        kernel traces and lowerings (``ops.attention.kernel_counts``)."""
        ways = [f.core_in_kernels for f in layers
                if f.core_in_kernels is not None]
        return {"attn_cores_kernel": sum(ways),
                "attn_cores_composed": len(ways) - sum(ways),
                **kernel_counts()}

    # -- pure compute ----------------------------------------------------------

    def apply(self, params, x):
        return self.apply_counted(params, x)[0]

    def apply_counted(self, p, x):
        import jax

        b, t, d = x.shape
        h, kv, hd = self.heads, self.kv_heads, self.head_dim
        with jax.named_scope("attn_qkv"):
            xn = rms_norm(x, p["norm_attn"], self.norm_eps)
            q = (xn @ p["wq"]).reshape(b, t, h, hd)
            k = (xn @ p["wk"]).reshape(b, t, kv, hd)
            v = (xn @ p["wv"]).reshape(b, t, kv, hd)
            rope = self.rope
            cos, sin = rope_tables(
                t, int(rope.get("rotary_dim", hd)), float(rope["theta"]),
                rope.get("yarn"))
            q, k = apply_rope(q, cos, sin), apply_rope(k, cos, sin)
            gate = (jax.nn.sigmoid(xn @ p["w_gate"]) if self.gating
                    else None)
        with jax.named_scope("attn_core"):
            o = blocked_attention(q, k, v, self.window)
        self.core_in_kernels = core_tiles(
            jax.default_backend(), q.shape, kv, q.dtype,
            self.window) is not None
        with jax.named_scope("attn_out"):
            if gate is not None:
                o = o * gate[..., None]
            x = x + o.reshape(b, t, h * hd) @ p["wo"]
        counters = {}
        if not self.sparse:
            with jax.named_scope("dense_ffn"):
                xn = rms_norm(x, p["norm_ffn"], self.norm_eps)
                y = moe.swiglu(xn, p["ffn_gate"], p["ffn_up"],
                               p["ffn_down"])
            return x + y, counters
        with jax.named_scope("router"):
            xn = rms_norm(x, p["norm_ffn"], self.norm_eps).reshape(b * t, d)
            experts, weights = moe.route(xn, p["router"],
                                         self.experts_per_token,
                                         self.routed_scale)
        with jax.named_scope("shared_expert"):
            y = moe.swiglu(xn, p["shared_gate"], p["shared_up"],
                           p["shared_down"])
        part, counters = moe.held_experts(
            xn, experts, weights, p["experts_gate"], p["experts_up"],
            p["experts_down"], self.first_expert)
        with jax.named_scope("combine"):
            y = (y + part).reshape(b, t, d)
        return x + y, counters


class LMHead(_DeviceInitialised):
    """The final RMSNorm and the untied head over this chip's slice of the
    vocabulary: ``(batch, seq, hidden)`` -> ``(batch, seq, vocab)``.
    ``apply`` gives the per-position softmax like ``SeqAll2AllSoftmax``;
    the fused trainer takes ``apply_logits`` (loss and cotangent derive
    from the logits in its loss head)."""

    decay_exempt = ("norm",)

    def __init__(self, workflow=None, name=None, vocab=256, norm_eps=1e-6,
                 **kwargs):
        super().__init__(workflow=workflow, name=name, **kwargs)
        self.vocab, self.norm_eps = int(vocab), float(norm_eps)

    @property
    def output_samples_number(self) -> int:
        return self.vocab

    def param_shapes(self):
        d = int(self.input.shape[-1])
        return {"norm": ((d,), None),
                "weights": ((self.vocab, d), self.init_std)}

    def output_shape_for(self, in_shape):
        return (in_shape[0], in_shape[1], self.vocab)

    def apply_logits(self, params, x):
        from znicz_tpu.ops.linear import seq_linear

        return seq_linear(rms_norm(x, params["norm"], self.norm_eps),
                          params["weights"])

    def apply(self, params, x):
        from znicz_tpu.ops import activations

        return activations.softmax(self.apply_logits(params, x))
