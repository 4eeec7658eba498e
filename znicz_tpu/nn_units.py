"""NN unit bases (rebuild of ``znicz/nn_units.py``, SURVEY.md §2.2 "NN base").

Two base classes:

  - ``ForwardBase`` — a unit with ``input -> output`` plus learnable params
    (weights/bias), weight init policies (uniform/gaussian ``weights_stddev``),
    ``weights_transposed``, and a pure ``apply(params, x)`` the whole stack
    reuses (unit-at-a-time run, fused train step, numpy oracle tests).

  - ``GradientDescentBase`` — the reference's hand-written backward ("GD")
    units become a facade over ``jax.vjp`` of the paired forward's pure
    function.  What is preserved is the *semantics* the reference exposed:
    per-unit learning_rate / learning_rate_bias / weights_decay / l1_vs_l2 /
    gradient_moment (momentum) / gradient clipping, err_output -> err_input
    chaining in reverse unit order, and updates applied only on TRAIN
    minibatches.  What is gone: hand-derived derivative kernels (vjp cannot
    drift from the forward math).

TPU notes: each unit jits one step function with static shapes; parameters
and hyperparameters are traced arguments so per-epoch lr adjustment never
recompiles.
"""

from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import numpy as np

from znicz_tpu.core import prng
from znicz_tpu.core.units import Unit
from znicz_tpu.distributable import Distributable
from znicz_tpu.memory import Array


class ForwardBase(Unit, Distributable):
    """Base of every forward compute unit.

    Config kwargs (reference names):
      - ``weights_stddev``: init scale; default ``1/sqrt(fan_in)``-style.
      - ``weights_filling``: "uniform" | "gaussian" | "constant".
      - ``bias_stddev`` / ``bias_filling``: same for bias.
      - ``include_bias``: bias term on/off.
      - ``weights_transposed``: store W as (in, out) instead of (out, in).
    """

    #: subclasses with no learnable params set this False (pooling, dropout…)
    has_weights = True

    def __init__(self, workflow=None, name=None, **kwargs):
        super().__init__(workflow=workflow, name=name, **kwargs)
        self.input: Optional[Array] = None
        self.output = Array()
        self.weights = Array()
        self.bias = Array()
        self.weights_stddev = kwargs.get("weights_stddev")
        self.weights_filling = kwargs.get("weights_filling", "uniform")
        self.bias_stddev = kwargs.get("bias_stddev")
        self.bias_filling = kwargs.get("bias_filling", "constant")
        self.include_bias = kwargs.get("include_bias", True)
        self.weights_transposed = kwargs.get("weights_transposed", False)
        self._compiled = None

    # -- weight init ---------------------------------------------------------

    def _fill(self, arr: np.ndarray, filling: str, stddev: float) -> None:
        gen = prng.get(self.name)
        if filling == "uniform":
            # The reference's uniform filling spans ±stddev·sqrt(3) so that
            # the std matches the gaussian filling.
            lim = stddev * np.sqrt(3.0)
            gen.fill_uniform(arr, -lim, lim)
        elif filling == "gaussian":
            gen.fill_normal(arr, stddev)
        elif filling == "constant":
            arr[...] = stddev
        else:
            raise ValueError(f"unknown filling {filling!r}")

    def init_weights(self, w_shape: Tuple[int, ...],
                     b_shape: Tuple[int, ...]) -> None:
        fan_in = int(np.prod(w_shape[1:])) or 1
        stddev = self.weights_stddev or 1.0 / np.sqrt(fan_in)
        w = np.zeros(w_shape, np.float32)
        self._fill(w, self.weights_filling, stddev)
        if self.weights_transposed:
            w = np.ascontiguousarray(w.T)
        self.weights.mem = w
        if self.include_bias:
            b = np.zeros(b_shape, np.float32)
            self._fill(b, self.bias_filling, self.bias_stddev or 0.0)
            self.bias.mem = b

    # -- pure compute --------------------------------------------------------

    def params(self) -> Dict[str, Array]:
        """name -> Array of learnable params (used by GD twin, snapshotter,
        fused trainer)."""
        if not self.has_weights:
            return {}
        out = {"weights": self.weights}
        if self.include_bias:
            out["bias"] = self.bias
        return out

    def apply(self, params: Dict, x):
        """Pure forward: params dict of jax arrays + input -> output.
        Subclasses MUST override.  No side effects, jit-safe."""
        raise NotImplementedError

    def output_shape_for(self, in_shape: Tuple[int, ...]) -> Tuple[int, ...]:
        """Static output shape given input shape; subclasses override."""
        raise NotImplementedError

    # -- unit lifecycle ------------------------------------------------------

    def create_output(self) -> None:
        shape = self.output_shape_for(tuple(self.input.shape))
        if self.output.mem is None or tuple(self.output.shape) != shape:
            self.output.mem = np.zeros(shape, np.float32)

    def initialize(self, device=None, **kwargs):
        super().initialize(device=device, **kwargs)
        for arr in (self.weights, self.bias, self.output):
            arr.initialize(device)

    def run(self):
        if self._compiled is None:
            import jax
            self._compiled = jax.jit(self.apply)
        p = {k: a.devmem for k, a in self.params().items()}
        self.output.devmem = self._compiled(p, self.input.devmem)


def _decay_grad(w, weights_decay, l1_vs_l2):
    """Regularization gradient, reference formula: a weighted mix of L2 (w)
    and L1 (sign w) — ``factor_l1·sign(w)/2 + factor_l2·w`` with
    ``l1_vs_l2`` interpolating."""
    import jax.numpy as jnp

    return weights_decay * (l1_vs_l2 * 0.5 * jnp.sign(w)
                            + (1.0 - l1_vs_l2) * w)


def _state_dtype():
    """Storage dtype for optimizer accumulators (velocities):
    ``root.common.engine.state_dtype = "bfloat16"`` halves their HBM
    traffic — the profiled cost of the fc update fusions is pure
    weight+velocity memory bandwidth (r4 profile: fc6 dW+update at
    11 TFLOP/s, HBM-bound).  Update MATH stays float32 regardless
    (sgd_update); only the stored accumulator is rounded.  Semantics:
    the velocity is quantized to bf16 (8-bit mantissa) once per step;
    master weights are always float32."""
    from znicz_tpu.core.config import root

    name = root.common.engine.get("state_dtype", "float32")
    if name == "float32":
        return np.dtype("float32")
    if name == "bfloat16":
        return "bfloat16"
    raise ValueError(
        f"root.common.engine.state_dtype={name!r}: must be 'float32' or "
        "'bfloat16' (silently accepting a typo would silently change "
        "training-state precision)")


def sgd_update(w, g, v, *, lr, weights_decay, l1_vs_l2, momentum, clip):
    """The reference's weight-update kernel as one pure function — the
    SINGLE home of the update rule, used by both the unit-at-a-time GD units
    and the fused SPMD trainer (they must never drift).

    ``v`` may be stored in a reduced dtype (see ``_state_dtype``); the
    arithmetic runs in the weights' dtype (f32) and the new velocity is
    stored back in v's own dtype.  Returns (w_new, v_new)."""
    import jax.numpy as jnp

    g = jnp.where(clip > 0.0, jnp.clip(g, -clip, clip), g)
    g = g + _decay_grad(w, weights_decay, l1_vs_l2)
    v_new = momentum * v.astype(w.dtype) - lr * g
    return w + v_new, v_new.astype(v.dtype)


def adamw_update(w, g, m, v, *, lr, beta1, beta2, eps, weight_decay, step):
    """AdamW (Loshchilov & Hutter, 2019, algorithm 2) beside ``sgd_update``:
    ``m <- b1 m + (1 - b1) g``, ``v <- b2 v + (1 - b2) g^2``, bias
    correction by ``1 - b^step`` (``step`` counts from 1), and
    ``w <- w - lr (m^ / (sqrt(v^) + eps) + weight_decay w)`` — the decay
    is decoupled from the gradient.  Arithmetic in the weights' dtype
    (float32); the moments are stored back in their own dtype (see
    ``_state_dtype``).  Returns ``(w_new, m_new, v_new)``."""
    import jax.numpy as jnp

    g = g.astype(w.dtype)
    m_new = beta1 * m.astype(w.dtype) + (1.0 - beta1) * g
    v_new = beta2 * v.astype(w.dtype) + (1.0 - beta2) * jnp.square(g)
    m_hat = m_new / (1.0 - beta1 ** step)
    v_hat = v_new / (1.0 - beta2 ** step)
    w_new = w - lr * (m_hat / (jnp.sqrt(v_hat) + eps) + weight_decay * w)
    return w_new, m_new.astype(m.dtype), v_new.astype(v.dtype)


class GradientDescentBase(Unit, Distributable):
    """Backward twin of a ``ForwardBase``: consumes ``err_output``, produces
    ``err_input`` and updates the forward's params in place (on device).

    Hyperparameters (reference names / defaults):
      learning_rate (0.01), learning_rate_bias (= learning_rate),
      weights_decay (0.0), weights_decay_bias (0.0), l1_vs_l2 (0.0 = pure L2),
      gradient_moment (0.0), gradient_moment_bias (= gradient_moment),
      gradient_clip (0 = off; max-abs clip of raw gradients).

    Update rule: SGD with momentum + L1/L2 + clip — the policy every
    BASELINE config uses.  SURVEY §2.3 flags possible adagrad/adadelta
    accumulator variants in the reference's weight-update kernels as
    "verify against the mount"; the mount is empty, so those remain an
    explicit, documented drop until a reference to verify against exists.
    """

    def __init__(self, workflow=None, name=None, forward: ForwardBase = None,
                 **kwargs):
        super().__init__(workflow=workflow, name=name, **kwargs)
        self.forward = forward
        self.err_output: Optional[Array] = None     # linked from downstream
        self.err_input = Array()                     # produced for upstream
        self.learning_rate = kwargs.get("learning_rate", 0.01)
        self.learning_rate_bias = kwargs.get("learning_rate_bias",
                                             self.learning_rate)
        self.weights_decay = kwargs.get("weights_decay", 0.0)
        self.weights_decay_bias = kwargs.get("weights_decay_bias", 0.0)
        self.l1_vs_l2 = kwargs.get("l1_vs_l2", 0.0)
        self.gradient_moment = kwargs.get("gradient_moment", 0.0)
        self.gradient_moment_bias = kwargs.get("gradient_moment_bias",
                                               self.gradient_moment)
        self.gradient_clip = kwargs.get("gradient_clip", 0.0)
        #: when False, compute err_input but skip the param update (the
        #: reference's ``apply_gradient`` switch; also off for frozen layers)
        self.apply_gradient = kwargs.get("apply_gradient", True)
        #: first GD in the chain doesn't need err_input (reference's
        #: ``need_err_input``)
        self.need_err_input = kwargs.get("need_err_input", True)
        #: hypers as configured, frozen at first initialize() — the values a
        #: freshly built replica of this graph would carry.  The network
        #: digest hashes THESE, not the live fields, so a peer whose
        #: LearningRateAdjust schedule has advanced (slave re-registering
        #: mid-training) still matches the master's graph (ADVICE r3).
        self.initial_hypers = None
        self._velocities: Dict[str, Array] = {}
        self._compiled = None

    # -- pure compute --------------------------------------------------------

    def backward_apply(self, params: Dict, x):
        """The function whose vjp defines this unit's backward.  Defaults to
        the forward's ``apply``; softmax GD overrides (CE+softmax combo makes
        err_output already the logits cotangent)."""
        return self.forward.apply(params, x)

    def _step(self, params, velocities, x, err_output, hypers):
        """Pure: one backward+update step.  Returns (err_input, new_params,
        new_velocities)."""
        import jax

        (lr, lr_bias, wd, wd_bias, l1l2, mom, mom_bias, clip) = hypers
        _, vjp = jax.vjp(self.backward_apply, params, x)
        grads, err_input = vjp(err_output)
        new_params, new_vel = {}, {}
        for k, g in grads.items():
            is_bias = (k == "bias")
            new_params[k], new_vel[k] = sgd_update(
                params[k], g, velocities[k],
                lr=(lr_bias if is_bias else lr),
                weights_decay=(wd_bias if is_bias else wd),
                l1_vs_l2=l1l2,
                momentum=(mom_bias if is_bias else mom),
                clip=clip)
        return err_input, new_params, new_vel

    # -- unit lifecycle ------------------------------------------------------

    def initialize(self, device=None, **kwargs):
        super().initialize(device=device, **kwargs)
        assert self.forward is not None, f"{self.name}: no forward twin"
        if self.initial_hypers is None:
            self.initial_hypers = tuple(float(v) for v in self._hypers())
        self._make_state(device)
        self.err_input.initialize(device)

    def _make_state(self, device) -> None:
        """The optimizer's accumulators, one velocity a tensor."""
        for k, arr in self.forward.params().items():
            vel = Array(np.zeros(arr.shape, _state_dtype()))
            vel.initialize(device)
            self._velocities[k] = vel

    # -- Distributable: a GD unit's serializable state is its optimizer
    # -- accumulators (the forward owns the weights) --------------------------

    def _param_arrays(self):
        return {k: np.array(a.map_read())
                for k, a in self._velocities.items()}

    def apply_data_from_master(self, data):
        if data:
            for k, arr in self._velocities.items():
                if k in data:
                    arr.mem = np.asarray(data[k]).copy()

    apply_data_from_slave = apply_data_from_master

    def _hypers(self):
        import numpy as np

        return tuple(np.float32(v) for v in (
            self.learning_rate, self.learning_rate_bias, self.weights_decay,
            self.weights_decay_bias, self.l1_vs_l2, self.gradient_moment,
            self.gradient_moment_bias, self.gradient_clip))

    def run(self):
        if self._compiled is None:
            import jax
            self._compiled = jax.jit(self._step)
        params = {k: a.devmem for k, a in self.forward.params().items()}
        vels = {k: a.devmem for k, a in self._velocities.items()}
        err_in, new_params, new_vels = self._compiled(
            params, vels, self.forward.input.devmem, self.err_output.devmem,
            self._hypers())
        if self.need_err_input:
            self.err_input.devmem = err_in
        if self.apply_gradient:
            for k, arr in self.forward.params().items():
                arr.devmem = new_params[k]
            for k, arr in self._velocities.items():
                arr.devmem = new_vels[k]


class GradientDescentAdamW(GradientDescentBase):
    """Backward twin whose update rule is AdamW (``adamw_update``).

    Hyperparameters: ``learning_rate``, ``weights_decay`` (decoupled; the
    forward's ``decay_exempt`` tensors take none), ``beta1`` (0.9),
    ``beta2`` (0.95), ``epsilon`` (1e-8).  State, under the names the
    snapshotter and the fused trainer's ``velocities`` tree carry: the two
    moments of every tensor (``m_<tensor>``, ``v_<tensor>``, stored in
    ``root.common.engine.state_dtype``, float32 by default) and the
    ``step`` count.  The state is made on the device: two moments of a
    large model never cross the host link.

    The fused trainer does not ask what kind of GD unit this is: it calls
    ``apply_update`` where a unit has one and ``sgd_update`` where not."""

    def __init__(self, workflow=None, name=None, forward=None, **kwargs):
        super().__init__(workflow=workflow, name=name, forward=forward,
                         **kwargs)
        self.beta1 = kwargs.get("beta1", 0.9)
        self.beta2 = kwargs.get("beta2", 0.95)
        self.epsilon = kwargs.get("epsilon", 1e-8)

    def _hypers(self):
        return tuple(np.float32(v) for v in (
            self.learning_rate, self.weights_decay, self.beta1, self.beta2,
            self.epsilon))

    def _make_state(self, device) -> None:
        import jax
        import jax.numpy as jnp

        dtype = _state_dtype()
        shapes = {f"{moment}_{k}": (tuple(arr.shape), dtype)
                  for k, arr in self.forward.params().items()
                  for moment in ("m", "v")}
        shapes["step"] = ((), jnp.int32)
        # one program for the whole state, not one a tensor
        zeros = jax.jit(lambda: {key: jnp.zeros(shape, kind) for key,
                                 (shape, kind) in shapes.items()})()
        for key, value in zeros.items():
            arr = Array()
            arr.devmem = value
            arr.initialize(device)
            self._velocities[key] = arr

    def backward_apply(self, params, x):
        fn = getattr(self.forward, "apply_logits", self.forward.apply)
        return fn(params, x)

    def apply_update(self, params, grads, state, hypers):
        """Pure: ``(new_params, new_state)`` of one step."""
        lr, decay, beta1, beta2, eps = hypers
        exempt = getattr(self.forward, "decay_exempt", ())
        step = state["step"] + 1
        new_p, new_s = {}, dict(state, step=step)
        for k, w in params.items():
            w32 = w.astype("float32")       # bf16 masters update in f32
            w_new, new_s[f"m_{k}"], new_s[f"v_{k}"] = adamw_update(
                w32, grads[k], state[f"m_{k}"], state[f"v_{k}"], lr=lr,
                beta1=beta1, beta2=beta2, eps=eps,
                weight_decay=(0.0 if k in exempt else decay),
                step=step.astype("float32"))
            new_p[k] = w_new.astype(w.dtype)
        return new_p, new_s

    def _step(self, params, state, x, err_output, hypers):
        import jax

        if self.need_err_input:
            _, vjp = jax.vjp(self.backward_apply, params, x)
            grads, err_input = vjp(err_output)
        else:                               # integer ids take no cotangent
            _, vjp = jax.vjp(lambda p: self.backward_apply(p, x), params)
            (grads,), err_input = vjp(err_output), None
        return (err_input,) + self.apply_update(params, grads, state, hypers)
