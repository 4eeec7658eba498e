"""Learning-rate scheduling (rebuild of ``znicz/lr_adjust.py``).

Caffe-style policies applied to GD units over training iterations:

  - ``fixed``     — lr(t) = base
  - ``step``      — lr(t) = base · gamma^floor(t / step)
  - ``exp``       — lr(t) = base · gamma^t
  - ``inv``       — lr(t) = base · (1 + gamma·t)^(−power)
  - ``warmup``    — train step s runs at base · min(1, (s + 1) / steps):
                    a linear warm-up from the job's FIRST step
  - ``arbitrary`` — lr(t) = fn(base, t)

``LearningRateAdjust`` sits in the control graph after the GD chain (or the
decision in fused mode), counts train iterations, and writes the scheduled
lr into each bound GD unit's ``learning_rate``/``learning_rate_bias`` —
which both execution paths read per step (the fused step takes hypers as
traced arguments precisely so this never recompiles).

The clock: ``policy(base, it)`` is written after train step ``it`` and so
is the rate of step ``it + 1``; step 0 runs at ``policy.first(base)``,
which ``initialize`` writes — the base itself for every Caffe policy.
"""

from __future__ import annotations

from typing import Callable, List, Optional

from znicz_tpu.core.units import Unit


class LRPolicyBase:
    def __call__(self, base: float, it: int) -> float:
        raise NotImplementedError

    def first(self, base: float) -> float:
        """The rate of train step 0, before any step was counted."""
        return base


class FixedPolicy(LRPolicyBase):
    def __call__(self, base, it):
        return base


class StepPolicy(LRPolicyBase):
    def __init__(self, gamma=0.1, step=1000):
        self.gamma, self.step = float(gamma), int(step)

    def __call__(self, base, it):
        return base * self.gamma ** (it // self.step)


class ExpPolicy(LRPolicyBase):
    def __init__(self, gamma=0.999):
        self.gamma = float(gamma)

    def __call__(self, base, it):
        return base * self.gamma ** it


class InvPolicy(LRPolicyBase):
    def __init__(self, gamma=0.0001, power=0.75):
        self.gamma, self.power = float(gamma), float(power)

    def __call__(self, base, it):
        return base * (1.0 + self.gamma * it) ** (-self.power)


class WarmupPolicy(LRPolicyBase):
    """Linear warm-up: train step ``s`` runs at ``base * (s + 1) / steps``
    while ``s < steps`` and at ``base`` after."""

    def __init__(self, steps=2000):
        self.steps = int(steps)

    def rate(self, base, step):
        return base * min(1.0, (step + 1) / self.steps)

    def __call__(self, base, it):       # written after step ``it``
        return self.rate(base, it + 1)

    def first(self, base):
        return self.rate(base, 0)


class ArbitraryPolicy(LRPolicyBase):
    def __init__(self, fn: Callable[[float, int], float]):
        self.fn = fn

    def __call__(self, base, it):
        return self.fn(base, it)


POLICIES = {"fixed": FixedPolicy, "step": StepPolicy, "exp": ExpPolicy,
            "inv": InvPolicy, "warmup": WarmupPolicy}


def make_policy(name: str, **kwargs) -> LRPolicyBase:
    return POLICIES[name](**kwargs)


class LearningRateAdjust(Unit):
    """Bind with ``add_gd(gd_unit, policy [, bias_policy])``; each run()
    (one per train minibatch) advances the iteration counter and rewrites
    the bound units' learning rates."""

    def __init__(self, workflow=None, name=None, **kwargs):
        super().__init__(workflow=workflow, name=name, **kwargs)
        self.iteration = 0
        self._bindings: List[tuple] = []

    def add_gd(self, gd, policy: LRPolicyBase,
               bias_policy: Optional[LRPolicyBase] = None) -> None:
        self._bindings.append(
            (gd, float(gd.learning_rate), float(gd.learning_rate_bias),
             policy, bias_policy or policy))

    def _apply_first(self) -> None:
        for gd, base, base_bias, pol, bias_pol in self._bindings:
            gd.learning_rate = pol.first(base)
            gd.learning_rate_bias = bias_pol.first(base_bias)

    def initialize(self, **kwargs):
        super().initialize(**kwargs)
        if self.iteration == 0:
            self._apply_first()

    def _apply(self, it: int) -> None:
        for gd, base, base_bias, pol, bias_pol in self._bindings:
            gd.learning_rate = pol(base, it)
            gd.learning_rate_bias = bias_pol(base_bias, it)

    def run(self):
        self._apply(self.iteration)
        self.iteration += 1

    def restore_iteration(self, iteration: int) -> None:
        """Rewind the schedule to the state right after ``iteration`` many
        ``run()`` calls (the fused deep pipeline's speculation rollback):
        counter reset and the bound units' lrs rewritten accordingly —
        back to the configured bases for iteration 0."""
        self.iteration = int(iteration)
        if self.iteration > 0:
            self._apply(self.iteration - 1)
        else:
            self._apply_first()
