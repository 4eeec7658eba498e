"""Sequence-model units (beyond-reference capability; see
ops/attention.py for why).  Follows the framework's unit contract: pure
``apply(params, x)``, a GD twin via vjp with the standard per-layer
hyperparameters, registry type ``"attention"`` for StandardWorkflow.

Input/output: (batch, seq, embed).  For sequence-parallel training, the
fused path can swap the core for ``ops.attention.ring_attention`` inside a
shard_map over the sequence axis — either explicitly (``sp_axis`` kwarg,
for callers already inside a shard_map) or via the
``root.common.engine.seq_parallel`` knob (ISSUE 15): with ``seq_parallel
= N > 1`` the unit builds an ``("sp",)`` mesh of N devices at initialize
and ``apply`` shard_maps the attention core over it — ring attention
leaves the dryrun on the EXISTING mesh plumbing, CPU-testable with
virtual devices (default 0 = off, the single-device path, bit-exact;
tests/test_attention.py).

The variable-length serving/training units live here too (ISSUE 15):

  - :class:`CharEmbedding` — (batch, seq) integer ids -> token + position
    embeddings; the id dtype crossing the wire/HBM is u8 (vocab <= 256),
    decoded in-graph like every u8 dataset;
  - :class:`SeqAll2All` family — POSITION-WISE dense layers (the
    transformer FFN / logits head): same (out, in) weight layout and
    activation surface as All2All, applied at every sequence position
    instead of over the flattened sample (All2All's flatten is exactly
    what a variable-length input cannot have).
"""

from __future__ import annotations

from typing import Dict

import numpy as np

from znicz_tpu.memory import Array
from znicz_tpu.nn_units import ForwardBase, GradientDescentBase
from znicz_tpu.ops import activations
from znicz_tpu.ops.attention import (attention, cache_append,
                                     decode_attention, ring_attention)


def seq_parallel_size() -> int:
    """The ``root.common.engine.seq_parallel`` knob: sequence-parallel
    mesh size for MultiHeadAttention (0/1 = off — the single-device
    path).  Gated OFF by default."""
    from znicz_tpu.core.config import root

    return int(root.common.engine.get("seq_parallel", 0))


class MultiHeadAttention(ForwardBase):
    def __init__(self, workflow=None, name=None, heads=4, head_dim=None,
                 causal=False, sp_axis=None, residual=False, **kwargs):
        super().__init__(workflow=workflow, name=name, **kwargs)
        self.heads = int(heads)
        self.head_dim = head_dim           # default: embed // heads
        self.causal = bool(causal)
        self.sp_axis = sp_axis             # set inside shard_map for SP
        #: y = x + attn(x): the transformer block's skip connection,
        #: inside the unit so the strictly-sequential forward chain
        #: (unit engine AND fused path) needs no graph surgery
        self.residual = bool(residual)
        #: ("sp",) mesh when root.common.engine.seq_parallel is on
        #: (built at initialize; apply shard_maps the core over it) — or
        #: a TRAINER mesh via bind_sequence_mesh (ISSUE 18)
        self._sp_mesh = None
        #: (batch axis or None, sequence axis) the shard_map splits over
        self._sp_spec = (None, "sp")
        self.proj = {k: Array() for k in ("wq", "wk", "wv", "wo")}

    def bind_sequence_mesh(self, mesh, batch_axis="data",
                           seq_axis="model") -> bool:
        """Ring attention on a TRAINER/SERVING mesh (ISSUE 18): instead
        of a private ("sp",) mesh, shard_map the attention core over the
        slice's own axes — batch over ``batch_axis``, sequence blocks
        ring-rotating over ``seq_axis`` — so charlm training reuses the
        very mesh its train steps are jitted over (no second device
        grid, no resharding at the attention boundary).  Sticky:
        ``initialize`` skips its private mesh once bound.  Returns False
        (unbound) when the mesh lacks a >1 sequence axis."""
        if mesh is None or seq_axis not in mesh.axis_names \
                or int(mesh.shape[seq_axis]) < 2:
            return False
        self._sp_mesh = mesh
        self._sp_spec = (batch_axis if batch_axis in mesh.axis_names
                         else None, seq_axis)
        return True

    def params(self) -> Dict[str, Array]:
        return dict(self.proj)

    def output_shape_for(self, in_shape):
        return tuple(in_shape)

    def _core(self, q, k, v, axis_name=None):
        if axis_name:
            return ring_attention(q, k, v, axis_name, causal=self.causal)
        return attention(q, k, v, causal=self.causal)

    def apply(self, params, x):
        b, t, e = x.shape
        h, d = self.heads, self.head_dim
        q = (x @ params["wq"]).reshape(b, t, h, d)
        k = (x @ params["wk"]).reshape(b, t, h, d)
        v = (x @ params["wv"]).reshape(b, t, h, d)
        bax, sax = self._sp_spec
        if self.sp_axis:
            o = self._core(q, k, v, self.sp_axis)
        elif (self._sp_mesh is not None
                and t % self._sp_mesh.shape[sax] == 0
                and (bax is None or b % self._sp_mesh.shape[bax] == 0)):
            # ring attention over the bound mesh — q/k/v split along the
            # sequence axis (and the batch axis when bound to a trainer
            # mesh), k/v blocks rotate by ppermute, grads flow through
            # the shard_map (tests/test_attention.py proves exactness +
            # grad parity).  A shape the mesh cannot split (a short
            # serving bucket) falls back to the dense core — same math.
            import jax
            from jax.sharding import PartitionSpec as P

            spec = P(bax, sax)
            o = jax.shard_map(
                lambda q, k, v: self._core(q, k, v, sax),
                mesh=self._sp_mesh,
                in_specs=(spec, spec, spec), out_specs=spec)(q, k, v)
        else:
            o = self._core(q, k, v)
        y = o.reshape(b, t, h * d) @ params["wo"]
        return x + y if self.residual else y

    def apply_prefill(self, params, x):
        """Full-sequence forward that ALSO returns the per-position k/v
        it computed, so the serving plane can seed a decode cache from
        the prompt in one pass (ISSUE 16).  Dense core only — a prefill
        bucket is one device's worth of sequence.  Returns
        (y, k, v) with k/v shaped (batch, seq, heads, head_dim)."""
        b, t, e = x.shape
        h, d = self.heads, self.head_dim
        q = (x @ params["wq"]).reshape(b, t, h, d)
        k = (x @ params["wk"]).reshape(b, t, h, d)
        v = (x @ params["wv"]).reshape(b, t, h, d)
        o = attention(q, k, v, causal=self.causal)
        y = o.reshape(b, t, h * d) @ params["wo"]
        return (x + y if self.residual else y), k, v

    def apply_prefill_chunk(self, params, x, k_view, v_view, t0):
        """Chunked prefill (ISSUE 19): ``x`` is one chunk's
        (batch, chunk, embed) hiddens whose row ``i`` sits at GLOBAL
        positions ``t0[i] .. t0[i] + chunk - 1``; ``k_view``/``v_view``
        are (batch, ctx, heads, head_dim) gathered paged-cache views
        already holding each row's prefix ``[0 .. t0)``.  Writes the
        chunk's k/v at its global positions (positions past the view
        drop), attends causally with per-row offsets — positions past
        each query (a reused page's stale tail, pad tokens' keys) are
        causally dead — and returns ``(y, k_rows, v_rows)`` for the
        caller to persist into the paged pool."""
        import jax.numpy as jnp

        b, c, e = x.shape
        h, d = self.heads, self.head_dim
        q = (x @ params["wq"]).reshape(b, c, h, d)
        k_rows = (x @ params["wk"]).reshape(b, c, h, d)
        v_rows = (x @ params["wv"]).reshape(b, c, h, d)
        idx = t0[:, None] + jnp.arange(c)
        rows_b = jnp.arange(b)[:, None]
        k_cache = k_view.at[rows_b, idx].set(k_rows, mode="drop")
        v_cache = v_view.at[rows_b, idx].set(v_rows, mode="drop")
        o = attention(q, k_cache, v_cache, causal=True, q_offset=t0)
        y = o.reshape(b, c, h * d) @ params["wo"]
        return (x + y if self.residual else y), k_rows, v_rows

    def apply_decode(self, params, x_t, k_cache, v_cache, t):
        """One autoregressive step: ``x_t`` is this step's hidden row
        (batch, 1, embed) at per-row global position ``t`` ((batch,)
        int32); caches are (batch, cache_len, heads, head_dim).  Appends
        this step's k/v at position ``t`` (so the query always sees at
        least itself), attends over the prefix ``[0..t]``, and returns
        ``(y_t, k_row, v_row)`` — the new rows, for the caller to
        persist (the serving pool scatters just the row, not the whole
        gathered cache).  The returned ``k_cache``/``v_cache`` are the
        appended versions used for THIS step's attention."""
        b, _, e = x_t.shape
        h, d = self.heads, self.head_dim
        q = (x_t @ params["wq"]).reshape(b, 1, h, d)
        k_row = (x_t @ params["wk"]).reshape(b, h, d)
        v_row = (x_t @ params["wv"]).reshape(b, h, d)
        k_cache = cache_append(k_cache, k_row, t)
        v_cache = cache_append(v_cache, v_row, t)
        o = decode_attention(q, k_cache, v_cache, t)
        y = o.reshape(b, 1, h * d) @ params["wo"]
        return (x_t + y if self.residual else y), k_row, v_row

    def initialize(self, device=None, **kwargs):
        b, t, e = self.input.shape
        if self.head_dim is None:
            assert e % self.heads == 0, \
                f"{self.name}: embed {e} not divisible by heads {self.heads}"
            self.head_dim = int(e) // self.heads
        sp = seq_parallel_size()
        if sp > 1 and self.sp_axis is None and self._sp_mesh is None:
            if int(t) % sp:
                raise ValueError(
                    f"{self.name}: root.common.engine.seq_parallel={sp} "
                    f"cannot split sequence length {t}; pick a seq "
                    f"length divisible by the sp mesh size")
            from znicz_tpu.parallel.mesh import make_mesh

            self._sp_mesh = make_mesh((sp,), ("sp",))
        hd = self.heads * self.head_dim
        if self.proj["wq"].mem is None:
            for key, shape in (("wq", (int(e), hd)), ("wk", (int(e), hd)),
                               ("wv", (int(e), hd)), ("wo", (hd, int(e)))):
                w = np.zeros(shape, np.float32)
                self._fill(w, self.weights_filling,
                           self.weights_stddev or 1.0 / np.sqrt(shape[0]))
                self.proj[key].mem = w
        self.create_output()
        for arr in self.proj.values():
            arr.initialize(device)
        super().initialize(device=device, **kwargs)


class GDMultiHeadAttention(GradientDescentBase):
    """vjp of the attention forward; per-layer lr/momentum/decay as usual."""


class CharEmbedding(ForwardBase):
    """Token + positional embedding: (batch, seq) integer ids ->
    (batch, seq, embed).  Ids may arrive as floats (the u8 storage
    decode widens in-graph like every u8 dataset) — they are cast back
    to int32 for the table lookup, so the SAME pure function serves the
    trainer's gathered rows and the serving plane's staged buckets.
    Positions index from 0: a request padded on the RIGHT keeps its real
    tokens' positions unchanged, which is what the masked-parity
    contract needs."""

    def __init__(self, workflow=None, name=None, vocab=256, embed=64,
                 max_len=128, **kwargs):
        super().__init__(workflow=workflow, name=name, **kwargs)
        self.vocab = int(vocab)
        self.embed = int(embed)
        self.max_len = int(max_len)
        self.tables = {"embed": Array(), "pos": Array()}

    def params(self) -> Dict[str, Array]:
        return dict(self.tables)

    def output_shape_for(self, in_shape):
        return (in_shape[0], in_shape[1], self.embed)

    def apply(self, params, x):
        import jax.numpy as jnp

        ids = jnp.clip(x.astype(jnp.int32), 0, self.vocab - 1)
        t = x.shape[1]
        return jnp.take(params["embed"], ids, axis=0) \
            + params["pos"][:t][None]

    def apply_offset(self, params, x, t0):
        """A chunk's embedding at per-row global offsets (ISSUE 19's
        chunked prefill): ``x`` is (batch, chunk) ids whose row ``i``
        sits at positions ``t0[i] .. t0[i] + chunk - 1``.  Same tables,
        same clip as :meth:`apply`; positions are gathered per row (and
        clip at the table top like apply_decode — pad tokens past the
        window read a valid row whose output is discarded)."""
        import jax.numpy as jnp

        ids = jnp.clip(x.astype(jnp.int32), 0, self.vocab - 1)
        pos = jnp.clip(t0[:, None] + jnp.arange(x.shape[1]), 0,
                       self.max_len - 1)
        return jnp.take(params["embed"], ids, axis=0) \
            + jnp.take(params["pos"], pos, axis=0)

    def apply_decode(self, params, tokens, t):
        """One decode step's embedding: ``tokens`` is (batch,) — this
        step's input id per row — at per-row global position ``t``
        ((batch,) int32).  Returns (batch, 1, embed).  Same tables, same
        clip, but the position is gathered per ROW instead of sliced
        from 0 (each co-batched generation sits at its own depth)."""
        import jax.numpy as jnp

        ids = jnp.clip(tokens.astype(jnp.int32), 0, self.vocab - 1)
        pos = jnp.clip(t, 0, self.max_len - 1)
        return (jnp.take(params["embed"], ids, axis=0)
                + jnp.take(params["pos"], pos, axis=0))[:, None, :]

    def initialize(self, device=None, **kwargs):
        b, t = self.input.shape[:2]
        if int(t) > self.max_len:
            raise ValueError(
                f"{self.name}: input seq length {t} exceeds max_len="
                f"{self.max_len} (the positional table's size)")
        if self.tables["embed"].mem is None:
            for key, shape in (("embed", (self.vocab, self.embed)),
                               ("pos", (self.max_len, self.embed))):
                w = np.zeros(shape, np.float32)
                self._fill(w, self.weights_filling,
                           self.weights_stddev or 1.0 / np.sqrt(self.embed))
                self.tables[key].mem = w
        self.create_output()
        for arr in self.tables.values():
            arr.initialize(device)
        super().initialize(device=device, **kwargs)


class GDCharEmbedding(GradientDescentBase):
    """vjp of the embedding lookup (scatter-add into the tables); the id
    input is integral, so no err_input flows upstream (none exists)."""


class SeqAll2All(ForwardBase):
    """Position-wise dense layer: ``y = act(x @ W^T + b)`` at every
    sequence position — (batch, seq, in) -> (batch, seq, width).  Same
    (out, in) weight layout, activation surface and GD semantics as
    All2All; what differs is exactly the flatten All2All performs (a
    variable-length input must keep its seq axis)."""

    ACTIVATION = staticmethod(activations.identity)

    def __init__(self, workflow=None, name=None, output_sample_shape=(),
                 **kwargs):
        super().__init__(workflow=workflow, name=name, **kwargs)
        if isinstance(output_sample_shape, int):
            output_sample_shape = (output_sample_shape,)
        self.width = int(np.prod(tuple(output_sample_shape))) \
            if output_sample_shape else 0

    def output_shape_for(self, in_shape):
        return (in_shape[0], in_shape[1], self.width)

    @property
    def output_samples_number(self) -> int:
        """Per-position width (the All2All-compat name the fused
        trainer's confusion sizing reads)."""
        return self.width

    def apply(self, params, x):
        from znicz_tpu.ops.linear import seq_linear

        return type(self).ACTIVATION(
            seq_linear(x, params["weights"], params.get("bias"),
                       weights_transposed=self.weights_transposed))

    def initialize(self, device=None, **kwargs):
        in_size = int(self.input.shape[-1])
        if not self.width:
            self.width = in_size
        if self.weights.mem is None:
            self.init_weights((self.width, in_size), (self.width,))
        self.create_output()
        super().initialize(device=device, **kwargs)


class SeqAll2AllTanh(SeqAll2All):
    ACTIVATION = staticmethod(activations.tanh_scaled)


class SeqAll2AllStrictRELU(SeqAll2All):
    ACTIVATION = staticmethod(activations.strict_relu)


class SeqAll2AllSoftmax(SeqAll2All):
    """Per-position softmax head (the LM's next-token distribution); the
    paired GD twin treats err_output as the logits cotangent, and the
    fused trainer emits LOGITS from this head exactly as it does for
    All2AllSoftmax."""

    ACTIVATION = staticmethod(activations.softmax)


class GDSeqAll2All(GradientDescentBase):
    """Backward for any SeqAll2All* via vjp of forward.apply."""


class GDSeqSoftmax(GDSeqAll2All):
    """err_output is d(CE)/d(logits): bypass the softmax in the vjp
    (the same fused softmax+CE-backward convention as gd.GDSoftmax)."""

    def backward_apply(self, params, x):
        from znicz_tpu.ops.linear import seq_linear

        return seq_linear(x, params["weights"], params.get("bias"),
                          weights_transposed=self.forward.weights_transposed)
