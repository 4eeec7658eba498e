"""Decoder-stack units: a token embedding, a decoder layer (a mixer —
an attention block or a state-space scan — and a dense or sparse-expert
feed-forward, each merged into the residual stream; a layer may hold ONE
of the two parts alone) and the head (final RMSNorm and the logits, over
a tensor of its own or TIED to the embedding's), built from the keys of a
model's public configuration — data, not code: a second decoder is a
second dictionary (``samples/laguna.py`` ``MODELS`` holds them), and what
two decoders do differently the LAYER chooses by its keys (``mixer``,
``feed_forward``, ``attention``, ``router``, ``activation``,
``residual_scale``, ``shared_width``), everything else being one code
path.

They follow the framework's unit contract (pure ``apply(params, x)``,
``params()`` a dict of ``Array``s, a GD twin, registry types
``token_embedding`` / ``decoder_layer`` / ``lm_head`` for
``StandardWorkflow``) with things the older units do not have, all of
which the fused trainer OBSERVES rather than is told:

  - a unit with many tensors: ``DecoderLayer.params()`` holds its dozens,
    ``decay_exempt`` names those weight decay skips (derived from the
    layer's keys: norms, gates, a linear router, scales, shifts,
    temperatures, biases);
  - a unit that counts, moves a tensor of its own, and hands the next
    one MORE than its output: ``apply_carried(params, x, carry)`` returns
    ``(y, carry, counters)``.  ``counters["moves"]``, where there is one,
    is ``{tensor key: step}``: what a TRAIN step adds to tensors of this
    unit that the loss has no gradient for (the router's selection bias,
    moved by the load) — a tensor like the others in the trainer's trees
    and in a snapshot, taken out of the counters before they leave the
    step (``FusedTrainer.forward_pass`` / ``_update_core``).
    The counters are small int32 arrays that leave the device with the
    step's loss, in the same pull (``FusedTrainer.loss_and_metrics`` /
    ``_book_counted``); ``carry`` is ``None`` until a unit makes one
    (here the router's state, which layer ``l`` adds to its own before it
    routes) and goes through per-unit rematerialisation beside ``x``
    (``apply_counted(params, x)`` is the same without a carry);
  - a unit that BORROWS a tensor (``borrowed``: its key -> the owning
    unit's name and key): the tied head reads the embedding's ``embed``.
    The tensor stands once in the trainer's trees and in a snapshot, has
    one optimizer state, decays once, and its gradient is the sum of both
    uses because the step differentiates one tree;
  - a last unit that takes the loss itself (``apply_loss``: ``(loss sum,
    errors)``, never logits): where the logits would not fit ``LMHead``
    runs norm, logits, log-sum-exp, target logit and argmax a BLOCK of
    rows at a time, and the gradient of the block with it, so that no
    ``rows x vocab`` array outlives a block (``head_loss``; blocks by one
    rule, ``loss_blocks``);
  - a unit that notes how it was traced: ``run_stats(units)`` turns what
    the units of a run noted on the host (which way each attention core
    runs, ``ops.attention.core_tiles``; how often the process traced and
    lowered the core's kernels; how many layers received a state; the
    head's blocks) into ``FusedTrainer.stats`` when the run ends — no
    device work, nothing in a step;
  - a unit that asks for rematerialisation (``remat = True``) and says
    what it keeps beside its input (``remat_keeps``, names of tensors:
    ``ops.attention.CORE_KEEPS``).  Training keeps a decoder layer's input
    (67 MB a layer at 16,384 tokens of width 2,048), its attention core's
    output (as many bytes as its queries: 201 or 268 MB at Laguna's 48 or
    64 heads of 128, 67 MB at ZAYA's 8 over 32,768 rows) and the core's
    float32 log-sum-exp (one a query and head), and recomputes the rest
    on the way back: norms, projections, rotations, mixing, router and
    experts run again, the core's forward kernel does not.

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
from znicz_tpu.ops import cca, moe, ssm
from znicz_tpu.ops.attention import (CORE_KEEPS, apply_rope,
                                     blocked_attention, core_tiles,
                                     kernel_counts, rope_tables)


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
    ``key -> (shape, stddev)`` (stddev ``None``: ones, a gain; ``0``:
    zeros, a bias; a function ``(key, shape) -> float32 array``: a
    tensor with a start of its own) and made on the device."""

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
                          std(jax.random.fold_in(base, i), shape)
                          if callable(std) else
                          jnp.zeros(shape, jnp.float32) if std == 0 else
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


#: the rotation of a layer that is told none of its own; ``rope=None``
#: is a layer WITHOUT rotation
DEFAULT_ROPE = {"theta": 10000.0}


class DecoderLayer(_DeviceInitialised):
    """One decoder layer::

        h = merge(x, Mix(RMSNorm(x))),    y = merge(h, FFN(RMSNorm(h)))

    or ONE of the two parts alone: ``mixer`` is ``"attention"``,
    ``"mamba"`` or ``None`` (no mixer: the layer is its feed-forward),
    ``feed_forward`` ``False`` leaves the mixer alone.

    ``merge(x, a)`` is ``x + a``, or with ``residual_scale`` ``(x + b_r) *
    s_r + (a + b_h) * s_h``: four learned vectors a sub-block, scales 1
    and shifts 0 at the start.

    ``Mix`` ``"mamba"`` (``ops.ssm``): ``[z | xBC | dt] = x^ W_in``; the
    ``xBC`` channels through a causal depthwise convolution of
    ``conv_kernel`` taps (with a bias) and a SiLU, then split into ``x``
    (``ssm_heads`` heads of ``ssm_head_dim``) and ``B``, ``C``
    (``ssm_groups`` groups of ``ssm_state``); ``dt = softplus(dt +
    dt_bias)`` in float32; the scan ``S_t = exp(dt_t A_h) S_{t-1} + dt_t
    x_t B_t^T``, ``y_t = S_t C_t + D_h x_t`` in chunks of ``ssm_chunk``
    positions (``A_h = -exp(A_log_h)``); ``RMSNorm over groups (y *
    silu(z))`` and ``W_out``.  At the start ``dt_bias`` is the inverse
    softplus of a ``dt`` drawn log-uniform in ``dt_range``'s first two
    numbers and floored at its third, ``A_log = log U[1, 16]``, ``D = 1``,
    the taps uniform in ``+-1 / sqrt(conv_kernel)``.

    ``Attn``: grouped-query (``heads`` query heads read ``kv_heads``),
    no biases, rotary positions on the first ``rotary_dim`` dimensions of
    every head (``rope``: ``theta`` and optionally the YaRN numbers —
    ``ops.attention.rope_tables``; ``rope=None``: no rotation at all),
    causal and, with ``window``, limited
    to the last ``window`` keys (``ops.attention.blocked_attention``).
    What stands between the projections and the core is chosen by
    ``attention``: ``"plain"`` — q, k, v as projected; with ``gating``
    head ``h``'s output is multiplied by ``sigmoid(x^ . w_gate[:, h])``,
    ``x^`` the normed input; ``"cca"`` — queries and keys mixed along the
    sequence by two causal convolutions of ``mixing_taps`` taps, a
    query-key mean, half the value heads shifted by a token, q and k
    normalised with a learned temperature (``ops.cca``).

    ``FFN``: ``dense_width`` set — a SwiGLU of that width; else the expert
    layer: ``experts_total`` routed experts of ``expert_width`` with
    ``experts_per_token`` a token plus, with ``shared_width``, one shared
    expert.  ``activation``: ``"swiglu"`` — three matrices an expert,
    ``(silu(x W_g) * x W_u) W_d``; ``"relu2"`` — two, ``relu(x W_u)^2
    W_d``, for routed, shared and dense alike.  ``router``:
    ``"sigmoid"`` — a linear map (``ops.moe.route``:
    weights ``routed_scale * s / sum of the chosen``; with
    ``selection_bias`` the choice is by ``s + router_bias``,
    ``ops.moe.route_balanced``); ``"mlp"`` — an MLP
    of ``router_width`` on a state that is this layer's down-projection
    plus, with ``receives_state``, ``gamma *`` the state the layer before
    handed on (``ops.moe.router_state`` / ``route_mlp``: a softmax, the
    chosen expert's probability its weight); the state goes on to the
    next layer as ``apply_carried``'s carry; the choice is by probability
    plus ``router_bias``).  ``router_bias``, in either router, is a
    tensor that no gradient reaches: every train step moves it by what
    that step's load asks (``ops.moe.balance_step``; the counters' entry
    ``moves``).  The layer
    is TOLD what it holds — ``experts_held`` experts from
    ``first_expert`` — routes over all ``experts_total``, and adds its own
    experts' part
    (``ops.moe.held_experts``); what the experts that live on other chips
    would add is left out, here and in the reference alike."""

    remat = True

    def __init__(self, workflow=None, name=None, heads=4, kv_heads=2,
                 head_dim=16, window=None, rope=DEFAULT_ROPE, gating=False,
                 dense_width=0, expert_width=0, shared_width=0,
                 experts_total=0, experts_held=0, first_expert=0,
                 experts_per_token=0, routed_scale=1.0, norm_eps=1e-6,
                 attention="plain", mixing_taps=(2, 2), router="sigmoid",
                 router_width=0, receives_state=False,
                 residual_scale=False, mixer="attention",
                 feed_forward=True, activation="swiglu",
                 selection_bias=False, out_scale=1.0, ssm_heads=0, ssm_head_dim=0, ssm_groups=1,
                 ssm_state=0, conv_kernel=4, ssm_chunk=128,
                 dt_range=(0.001, 0.1, 1e-4), **kwargs):
        super().__init__(workflow=workflow, name=name, **kwargs)
        self.heads, self.kv_heads = int(heads), int(kv_heads)
        self.head_dim = int(head_dim)
        self.window = int(window) if window else None
        self.rope = dict(rope) if rope else None
        self.mixer = str(mixer) if mixer else None
        self.feed_forward = bool(feed_forward)
        self.activation = str(activation)
        self.selection_bias = bool(selection_bias)
        self.out_scale = float(out_scale)
        self.ssm_heads, self.ssm_head_dim = int(ssm_heads), int(ssm_head_dim)
        self.ssm_groups, self.ssm_state = int(ssm_groups), int(ssm_state)
        self.conv_kernel, self.ssm_chunk = int(conv_kernel), int(ssm_chunk)
        self.dt_range = tuple(float(v) for v in dt_range)
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
        self.attention, self.router = str(attention), str(router)
        self.mixing_taps = tuple(int(t) for t in mixing_taps)
        self.router_width = int(router_width)
        self.receives_state = bool(receives_state)
        self.residual_scale = bool(residual_scale)
        if self.heads % self.kv_heads:
            raise ValueError(f"{self.name}: {self.heads} query heads do "
                             f"not divide over {self.kv_heads} KV heads")
        if self.attention not in ("plain", "cca") \
                or self.router not in ("sigmoid", "mlp") \
                or self.mixer not in ("attention", "mamba", None) \
                or self.activation not in ("swiglu", "relu2") \
                or not (self.mixer or self.feed_forward):
            raise ValueError(
                f"{self.name}: mixer {self.mixer!r}, attention "
                f"{self.attention!r}, feed_forward {self.feed_forward}, "
                f"router {self.router!r}, activation {self.activation!r}")
        if self.mixer == "mamba" and (
                self.ssm_groups < 1 or self.ssm_heads % self.ssm_groups
                or min(self.ssm_heads, self.ssm_head_dim,
                       self.ssm_state) < 1):
            raise ValueError(
                f"{self.name}: {self.ssm_heads} scan heads of "
                f"{self.ssm_head_dim} over {self.ssm_groups} groups of "
                f"state {self.ssm_state}")
        if self.feed_forward and not self.dense_width and not (
                0 < self.experts_held <= self.experts_total
                and 0 <= self.first_expert
                <= self.experts_total - self.experts_held
                and 0 < self.experts_per_token <= self.experts_total):
            raise ValueError(
                f"{self.name}: experts {self.first_expert}.."
                f"{self.first_expert + self.experts_held} of "
                f"{self.experts_total}, {self.experts_per_token} a token")
        self.hidden = 0             # the input's width, at initialize
        # noted when ``apply_carried`` traces
        self.core_in_kernels = None
        self.received_state = None
        self.scan_way, self.scan_chunks = None, 0
        #: what rematerialisation keeps of this unit beside its input: the
        #: attention core's output and log-sum-exp where the layer has a
        #: core; a scan or a feed-forward alone is made again from the input
        self.remat_keeps = CORE_KEEPS if self.mixer == "attention" else ()
        # noted by ``FusedTrainer.forward_pass`` when a train step traces:
        # the names it kept across this layer's rematerialisation
        self.remat_kept = ()

    @property
    def sparse(self) -> bool:
        """The layer holds routed experts (and counts their rows)."""
        return self.feed_forward and not self.dense_width

    def output_shape_for(self, in_shape):
        return tuple(in_shape)

    def _tensors(self):
        """``(key, shape, stddev, decays)`` of every tensor this layer's
        keys ask for, in the order the seeded stream makes them."""
        d, std = self.hidden, self.init_std
        # a part's output projection starts smaller where the layer is
        # told so (``out_scale``: a prenorm residual stream rescaled by
        # its depth)
        std_out = std * self.out_scale
        out = []
        if self.mixer == "attention":
            out += self._attention_tensors(std_out)
        elif self.mixer == "mamba":
            out += self._scan_tensors(std_out)
        if self.mixer:
            out += self._merge_tensors("attn")
        if not self.feed_forward:
            return out
        gated = self.activation == "swiglu"

        def mlp(prefix, lead, w):
            """A gated part's three matrices, or the two of ``relu2``."""
            return ([(f"{prefix}_gate", lead + (d, w), std, True)]
                    if gated else []) + [
                (f"{prefix}_up", lead + (d, w), std, True),
                (f"{prefix}_down", lead + (w, d), std_out, True)]

        out.append(("norm_ffn", (d,), None, False))
        if not self.sparse:
            return out + mlp("ffn", (), self.dense_width) \
                + self._merge_tensors("ffn")
        if self.router == "mlp":
            r = self.router_width
            out += [("router_down", (d, r), std, True),
                    ("router_down_b", (r,), 0, False)]
            if self.receives_state:
                out.append(("router_gamma", (r,), None, False))
            out += [("router_norm", (r,), None, False),
                    ("router_w1", (r, r), std, True),
                    ("router_b1", (r,), 0, False),
                    ("router_w2", (r, r), std, True),
                    ("router_b2", (r,), 0, False),
                    ("router_w3", (r, self.experts_total), std, True),
                    ("router_bias", (self.experts_total,), 0, False)]
        else:
            out.append(("router", (d, self.experts_total), std, False))
            if self.selection_bias:
                out.append(("router_bias", (self.experts_total,), 0, False))
        if self.shared_width:
            out += mlp("shared", (), self.shared_width)
        out += mlp("experts", (self.experts_held,), self.expert_width)
        return out + self._merge_tensors("ffn")

    def _attention_tensors(self, std_out):
        d, std = self.hidden, self.init_std
        hd, kvh = self.head_dim, self.kv_heads
        q, kv = self.heads * hd, kvh * hd
        out = [("norm_attn", (d,), None, False), ("wq", (d, q), std, True),
               ("wk", (d, kv), std, True), ("wv", (d, kv), std, True)]
        if self.attention == "cca":
            n, (t0, t1) = self.heads + kvh, self.mixing_taps
            out += [("mix_w", (t0, n, hd), std, True),
                    ("mix_b", (n, hd), 0, False),
                    ("mix_heads", (t1, n, hd, hd), std, True),
                    ("mix_heads_b", (n, hd), 0, False),
                    ("temp", (kvh,), 0, False)]
        if self.gating:
            out.append(("w_gate", (d, self.heads), std, False))
        return out + [("wo", (q, d), std_out, True)]

    def _scan_tensors(self, std_out):
        import jax
        import jax.numpy as jnp

        d, std, h = self.hidden, self.init_std, self.ssm_heads
        inner = h * self.ssm_head_dim
        mixed = inner + 2 * self.ssm_groups * self.ssm_state     # x, B, C
        lo, hi, floor = self.dt_range
        bound = self.conv_kernel ** -0.5

        def taps(key, shape):
            return jax.random.uniform(key, shape, jnp.float32, -bound, bound)

        def dt_bias(key, shape):
            dt = jnp.maximum(jnp.exp(jax.random.uniform(
                key, shape, jnp.float32, np.log(lo), np.log(hi))), floor)
            return dt + jnp.log(-jnp.expm1(-dt))        # softplus^-1(dt)

        def a_log(key, shape):
            return jnp.log(jax.random.uniform(key, shape, jnp.float32,
                                              1.0, 16.0))

        return [("norm_ssm", (d,), None, False),
                ("ssm_in", (d, inner + mixed + h), std, True),
                ("ssm_conv_w", (self.conv_kernel, mixed), taps, True),
                ("ssm_conv_b", (mixed,), 0, False),
                ("ssm_dt_bias", (h,), dt_bias, False),
                ("ssm_a_log", (h,), a_log, False),
                ("ssm_d", (h,), None, False),
                ("ssm_norm", (inner,), None, False),
                ("ssm_out", (inner, d), std_out, True)]

    def _merge_tensors(self, part: str):
        if not self.residual_scale:
            return []
        d = self.hidden
        return [(f"keep_scale_{part}", (d,), None, False),
                (f"keep_shift_{part}", (d,), 0, False),
                (f"new_scale_{part}", (d,), None, False),
                (f"new_shift_{part}", (d,), 0, False)]

    def param_shapes(self):
        return {key: (shape, std) for key, shape, std, _ in self._tensors()}

    @property
    def decay_exempt(self) -> tuple:
        return tuple(key for key, _, _, decays in self._tensors()
                     if not decays)

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
        last trace of ``apply_carried``), how many had their core's
        output and log-sum-exp kept across rematerialisation
        (``remat_kept``: none where no train step was traced), the
        process's count of kernel traces and lowerings
        (``ops.attention.kernel_counts``), where a router keeps a
        state, how many layers received one, where a router's selection
        bias is moved by the load, how many, and where a layer scans a
        state, the layers by kind, the scans by the way they ran and
        their chunks a row."""
        ways = [f.core_in_kernels for f in layers
                if f.core_in_kernels is not None]
        out = {"attn_cores_kernel": sum(ways),
               "attn_cores_composed": len(ways) - sum(ways),
               "attn_cores_kept": sum(
                   set(CORE_KEEPS) <= set(f.remat_kept) for f in layers),
               **kernel_counts()}
        if any(f.router == "mlp" for f in layers):
            out["router_states_carried"] = sum(
                bool(f.received_state) for f in layers)
        if any(f.moves_bias for f in layers):
            out["router_biases_moved"] = sum(f.moves_bias for f in layers)
        if any(f.mixer == "mamba" for f in layers):
            ways = [f.scan_way for f in layers if f.scan_way]
            out.update(
                layers_mamba=sum(f.mixer == "mamba" for f in layers),
                layers_attention=sum(f.mixer == "attention"
                                     for f in layers),
                layers_experts=sum(f.sparse for f in layers),
                ssm_scans_kernel=ways.count("kernel"),
                ssm_scans_composed=ways.count("composed"),
                ssm_chunks=max(f.scan_chunks for f in layers))
        return out

    @property
    def moves_bias(self) -> bool:
        """The layer's router chooses by a selection bias that every
        train step's load moves."""
        return self.sparse and (self.router == "mlp"
                                or self.selection_bias)

    # -- pure compute ----------------------------------------------------------

    def apply(self, params, x):
        return self.apply_counted(params, x)[0]

    def apply_counted(self, p, x):
        if self.receives_state:
            raise ValueError(f"{self.name} adds the state of the layer "
                             f"before it: apply_carried hands it over")
        y, _, counters = self.apply_carried(p, x, None)
        return y, counters

    def _merge(self, p, part: str, x, new):
        if not self.residual_scale:
            return x + new
        return ((x + p[f"keep_shift_{part}"]) * p[f"keep_scale_{part}"]
                + (new + p[f"new_shift_{part}"]) * p[f"new_scale_{part}"])

    def _core(self, q, k, v):
        import jax

        with jax.named_scope("attn_core"):
            o = blocked_attention(q, k, v, self.window)
        self.core_in_kernels = core_tiles(
            jax.default_backend(), q.shape, self.kv_heads, q.dtype,
            self.window) is not None
        return o

    def _rope_tables(self, t: int):
        rope = self.rope
        return rope_tables(t, int(rope.get("rotary_dim", self.head_dim)),
                           float(rope["theta"]), rope.get("yarn"))

    def _mix_scan(self, p, x):
        """The state-space mixer, merged: scopes ``ssm_in``, ``ssm_conv``,
        ``ssm_scan``, ``ssm_out``."""
        import jax
        import jax.numpy as jnp

        b, t, d = x.shape
        h, hd = self.ssm_heads, self.ssm_head_dim
        groups, n = self.ssm_groups, self.ssm_state
        inner, bc = h * hd, groups * n
        with jax.named_scope("ssm_in"):
            xn = rms_norm(x, p["norm_ssm"], self.norm_eps)
            z, mixed, dt = jnp.split(xn @ p["ssm_in"],
                                     [inner, 2 * inner + 2 * bc], axis=-1)
        with jax.named_scope("ssm_conv"):
            mixed = jax.nn.silu(ssm.causal_conv(mixed, p["ssm_conv_w"],
                                                p["ssm_conv_b"]))
            xs, bs, cs = jnp.split(mixed, [inner, inner + bc], axis=-1)
        with jax.named_scope("ssm_scan"):
            dt = jax.nn.softplus(dt.astype(jnp.float32)
                                 + p["ssm_dt_bias"].astype(jnp.float32))
            y = ssm.chunked_scan(
                xs.reshape(b, t, h, hd), dt, p["ssm_a_log"],
                bs.reshape(b, t, groups, n), cs.reshape(b, t, groups, n),
                p["ssm_d"], self.ssm_chunk)
            self.scan_way = ssm.STATS["way"]
            self.scan_chunks = ssm.STATS["chunks"]
        with jax.named_scope("ssm_out"):
            y = ssm.gated_norm(y.reshape(b, t, inner), z, p["ssm_norm"],
                               groups, self.norm_eps)
            return self._merge(p, "attn", x, y @ p["ssm_out"])

    def _attend_plain(self, p, x):
        import jax

        b, t, d = x.shape
        h, kv, hd = self.heads, self.kv_heads, self.head_dim
        with jax.named_scope("attn_qkv"):
            xn = rms_norm(x, p["norm_attn"], self.norm_eps)
            q = (xn @ p["wq"]).reshape(b, t, h, hd)
            k = (xn @ p["wk"]).reshape(b, t, kv, hd)
            v = (xn @ p["wv"]).reshape(b, t, kv, hd)
            if self.rope:
                cos, sin = self._rope_tables(t)
                q, k = apply_rope(q, cos, sin), apply_rope(k, cos, sin)
            gate = (jax.nn.sigmoid(xn @ p["w_gate"]) if self.gating
                    else None)
        o = self._core(q, k, v)
        with jax.named_scope("attn_out"):
            if gate is not None:
                o = o * gate[..., None]
            return self._merge(p, "attn", x, o.reshape(b, t, h * hd)
                               @ p["wo"])

    def _attend_cca(self, p, x):
        import jax

        b, t, d = x.shape
        h, kv, hd = self.heads, self.kv_heads, self.head_dim
        with jax.named_scope("cca_down"):
            xn = rms_norm(x, p["norm_attn"], self.norm_eps)
            q0 = (xn @ p["wq"]).reshape(b, t, h, hd)
            k0 = (xn @ p["wk"]).reshape(b, t, kv, hd)
            v = (xn @ p["wv"]).reshape(b, t, kv, hd)
        with jax.named_scope("cca_mix"):
            q, k, v = cca.mix(q0, k0, v, p, *self._rope_tables(t))
        o = self._core(q, k, v)
        with jax.named_scope("cca_up"):
            return self._merge(p, "attn", x, o.reshape(b, t, h * hd)
                               @ p["wo"])

    def apply_carried(self, p, x, carry):
        import jax

        b, t, d = x.shape
        if self.mixer == "mamba":
            x = self._mix_scan(p, x)
        elif self.mixer:
            x = (self._attend_cca if self.attention == "cca"
                 else self._attend_plain)(p, x)
        counters = {}
        if not self.feed_forward:
            return x, carry, counters

        def mlp(prefix, xn):
            if self.activation == "relu2":
                return moe.relu2(xn, p[f"{prefix}_up"], p[f"{prefix}_down"])
            return moe.swiglu(xn, p[f"{prefix}_gate"], p[f"{prefix}_up"],
                              p[f"{prefix}_down"])

        if not self.sparse:
            with jax.named_scope("dense_ffn"):
                y = mlp("ffn", rms_norm(x, p["norm_ffn"], self.norm_eps))
            return self._merge(p, "ffn", x, y), carry, counters
        with jax.named_scope("router"):
            xn = rms_norm(x, p["norm_ffn"], self.norm_eps).reshape(b * t, d)
            if self.router == "mlp":
                self.received_state = carry is not None
                if self.received_state != self.receives_state:
                    raise ValueError(
                        f"{self.name}: receives_state is "
                        f"{self.receives_state} and a state "
                        f"{'came' if self.received_state else 'did not'}")
                carry = moe.router_state(
                    xn, p["router_down"], p["router_down_b"],
                    p.get("router_gamma"), carry)
                experts, weights, move = moe.route_mlp(
                    carry, p["router_norm"], p["router_w1"], p["router_b1"],
                    p["router_w2"], p["router_b2"], p["router_w3"],
                    p["router_bias"], self.experts_per_token, self.norm_eps)
            elif self.selection_bias:
                experts, weights, move = moe.route_balanced(
                    xn, p["router"], p["router_bias"],
                    self.experts_per_token, self.routed_scale)
            else:
                experts, weights = moe.route(xn, p["router"],
                                             self.experts_per_token,
                                             self.routed_scale)
        y = None
        if self.shared_width:
            with jax.named_scope("shared_expert"):
                y = mlp("shared", xn)
        part, counters = moe.held_experts(
            xn, experts, weights, p.get("experts_gate"), p["experts_up"],
            p["experts_down"], self.first_expert)
        with jax.named_scope("combine"):
            y = (part if y is None else y + part).reshape(b, t, d)
        if self.moves_bias:
            counters["moves"] = {"router_bias": move}
        return self._merge(p, "ffn", x, y), carry, counters


def loss_blocks(rows: int, vocab: int, block_bytes: int = 1 << 30) -> tuple:
    """``(blocks, rows a block)`` the head's loss runs in — THE rule: as
    few blocks as keep a block's float32 logits within ``block_bytes`` (1
    GiB), of equal size, a multiple of 8 rows (the last block is padded
    with rows that count for nothing)."""
    blocks = max(-(-rows * vocab * 4 // block_bytes), 1)
    size = -(-rows // blocks)
    return blocks, (size if blocks == 1 else -(-size // 8) * 8)


def head_logits(x, gain, embed, eps: float):
    """Float32 logits ``RMSNorm(x) E^T`` of rows ``x`` ``(rows, hidden)``
    over ``embed`` ``(vocab, hidden)``: the product in the operands'
    dtype, accumulated and kept in float32."""
    import jax.numpy as jnp

    return jnp.einsum("rd,vd->rv", rms_norm(x, gain, eps), embed,
                      preferred_element_type=jnp.float32)


def blocked_head_loss(x, gain, embed, labels, valid, eps: float,
                      block: int):
    """``(sum over valid rows of -log softmax(RMSNorm(x) E^T)[label], rows
    whose argmax misses their label)``, ``block`` rows at a time: ``x``
    ``(rows, hidden)``, ``embed`` ``(vocab, hidden)``, ``labels`` int32
    and ``valid`` bool ``(rows,)``.  Differentiable in ``x``, ``gain`` and
    ``embed``: the gradient of a block is taken WITH its forward pass (the
    loss's cotangent is a scalar, so ``softmax - onehot`` is all the
    backward pass needs of the logits) and the backward pass only scales
    it — a block's logits are computed once and never kept.  The logits'
    cotangent goes into both products in ``x``'s dtype; the embedding's
    gradient is summed over the blocks in float32."""
    import jax
    import jax.numpy as jnp

    rows, hidden = x.shape
    blocks = -(-rows // block)
    pad = blocks * block - rows

    def split(a):
        a = jnp.pad(a, [(0, pad)] + [(0, 0)] * (a.ndim - 1))
        return a.reshape((blocks, block) + a.shape[1:])

    zero = jnp.zeros((), jnp.float32)

    def read(logits, lb, vb):
        logz = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, lb[:, None], axis=-1)[:, 0]
        miss = (jnp.argmax(logits, axis=-1) != lb) & vb
        return (jnp.sum(jnp.where(vb, logz - picked, 0.0)),
                jnp.sum(miss, dtype=jnp.float32), logz)

    @jax.custom_vjp
    def head(x, gain, embed, labels, valid):
        def step(acc, xs):
            xb, lb, vb = xs
            loss, miss, _ = read(head_logits(xb, gain, embed, eps), lb, vb)
            return (acc[0] + loss, acc[1] + miss), None

        return jax.lax.scan(step, (zero, zero), (
            split(x), split(labels), split(valid)))[0]

    def fwd(x, gain, embed, labels, valid):
        def step(acc, xs):
            xb, lb, vb = xs
            xn, norm_vjp = jax.vjp(lambda xb, gain: rms_norm(xb, gain, eps),
                                   xb, gain)
            logits = jnp.einsum("rd,vd->rv", xn, embed,
                                preferred_element_type=jnp.float32)
            loss, miss, logz = read(logits, lb, vb)
            hit = jnp.arange(logits.shape[-1])[None, :] == lb[:, None]
            dlogits = jnp.where(vb[:, None],
                                jnp.exp(logits - logz[:, None]) - hit,
                                0.0).astype(xn.dtype)
            dxn = jnp.einsum("rv,vd->rd", dlogits, embed,
                             preferred_element_type=jnp.float32)
            dembed = jnp.einsum("rv,rd->vd", dlogits, xn,
                                preferred_element_type=jnp.float32)
            dxb, dgain = norm_vjp(dxn.astype(xn.dtype))
            return (acc[0] + loss, acc[1] + miss,
                    acc[2] + dgain.astype(jnp.float32),
                    acc[3] + dembed), dxb

        (loss, miss, dgain, dembed), dx = jax.lax.scan(
            step, (zero, zero, jnp.zeros(gain.shape, jnp.float32),
                   jnp.zeros(embed.shape, jnp.float32)),
            (split(x), split(labels), split(valid)))
        dx = dx.reshape(blocks * block, hidden)[:rows]
        return (loss, miss), (dx, dgain.astype(gain.dtype),
                              dembed.astype(embed.dtype))

    def bwd(grads, ct):
        return tuple(g * ct[0].astype(g.dtype) for g in grads) + (None,
                                                                  None)

    head.defvjp(fwd, bwd)
    return head(x, gain, embed, labels, valid)


class LMHead(_DeviceInitialised):
    """The final RMSNorm and the head over this chip's slice of the
    vocabulary: ``(batch, seq, hidden)`` -> ``(batch, seq, vocab)``.  With
    ``tied`` the head owns the norm only and BORROWS the embedding's
    tensor (``borrowed``; ``tie_word_embeddings``), else it owns
    ``weights``.  ``apply`` gives the per-position softmax like
    ``SeqAll2AllSoftmax``; the fused trainer takes ``apply_loss``, which
    runs in as many blocks of rows as ``loss_blocks`` says for
    ``loss_block_bytes`` (a preset for tests shrinks it; nothing else
    does)."""

    decay_exempt = ("norm",)

    def __init__(self, workflow=None, name=None, vocab=256, norm_eps=1e-6,
                 tied=False, loss_block_bytes=1 << 30, **kwargs):
        super().__init__(workflow=workflow, name=name, **kwargs)
        self.vocab, self.norm_eps = int(vocab), float(norm_eps)
        self.tied = bool(tied)
        self.loss_block_bytes = int(loss_block_bytes)
        #: key of ``apply``'s params -> (owning unit's name, its key)
        self.borrowed = {}
        self.blocks_run = None      # noted when the loss is traced

    @property
    def output_samples_number(self) -> int:
        return self.vocab

    def param_shapes(self):
        d = int(self.input.shape[-1])
        shapes = {"norm": ((d,), None)}
        if not self.tied:
            shapes["weights"] = ((self.vocab, d), self.init_std)
        return shapes

    def initialize(self, device=None, **kwargs):
        if self.tied:
            owner = next(f for f in self.workflow.forwards
                         if isinstance(f, TokenEmbedding))
            if owner.vocab != self.vocab:
                raise ValueError(f"{self.name}: tied to {owner.name} of "
                                 f"{owner.vocab} ids, head of {self.vocab}")
            self.borrowed = {"weights": (owner.name, "embed")}
        super().initialize(device=device, **kwargs)

    def output_shape_for(self, in_shape):
        return (in_shape[0], in_shape[1], self.vocab)

    @staticmethod
    def run_stats(heads: list) -> dict:
        """The head's notes of a run: blocks its loss ran in (1: the
        logits whole) and tensors it borrows."""
        out = {"tied_tensors": sum(len(f.borrowed) for f in heads)}
        blocks = [f.blocks_run for f in heads if f.blocks_run is not None]
        if blocks:
            out["loss_blocks"] = sum(blocks)
        return out

    def blocks_for(self, in_shape) -> tuple:
        """``(blocks, rows a block)`` the loss of an input of ``in_shape``
        runs in."""
        return loss_blocks(int(np.prod(in_shape[:-1])), self.vocab,
                           self.loss_block_bytes)

    def apply_logits(self, params, x):
        from znicz_tpu.ops.linear import seq_linear

        return seq_linear(rms_norm(x, params["norm"], self.norm_eps),
                          params["weights"])

    def logits_rows(self, params, x):
        """Float32 logits of rows ``x`` ``(rows, hidden)``: what the loss
        reads of a block."""
        return head_logits(x, params["norm"], params["weights"],
                           self.norm_eps)

    def apply_loss(self, params, x, labels, batch_size):
        """``(loss sum, errors)`` over the first ``batch_size`` rows of
        ids; ``labels`` ``(batch, seq)``.  Where one block holds the
        logits they are built whole and differentiated by autodiff, the
        evaluator's math as ``FusedTrainer._loss_head`` writes it: a
        configuration whose logits fit (Laguna's 16,384 x 12,544) keeps
        the program it had, and pays neither a scan of one block nor
        float32 accumulators for a gradient that one product gives.
        Where not, a block of positions at a time
        (``blocked_head_loss``)."""
        import jax
        import jax.numpy as jnp

        b, t, d = x.shape
        self.blocks_run, block = self.blocks_for(x.shape)
        labels = labels.reshape(b * t).astype(jnp.int32)
        valid = jnp.repeat(jnp.arange(b) < batch_size, t)
        if self.blocks_run == 1:
            logits = self.apply_logits(params, x).astype(
                jnp.float32).reshape(b * t, self.vocab)
            logz = jax.nn.logsumexp(logits, axis=-1)
            picked = jnp.take_along_axis(logits, labels[:, None],
                                         axis=-1)[:, 0]
            loss = jnp.sum(jnp.where(valid, logz - picked, 0.0))
            miss = (jnp.argmax(logits, axis=-1) != labels) & valid
            return loss, jnp.sum(miss)
        with jax.named_scope("head_loss"):
            return blocked_head_loss(
                x.reshape(b * t, d), params["norm"], params["weights"],
                labels, valid, self.norm_eps, block)

    def apply(self, params, x):
        from znicz_tpu.ops import activations

        return activations.softmax(self.apply_logits(params, x))
