"""Workflow: a container Unit with a run loop (rebuild of ``veles/workflow.py``
+ ``veles/plumbing.py``).

Control semantics preserved from the reference: ``StartPoint`` fires first;
units fire when all their control predecessors fired (``Repeater`` fires when
*any* did, closing the training loop); ``EndPoint`` stops the workflow.
Execution is a deterministic single-threaded event queue (see units.py for
why the reference's thread pool was dropped).
"""

from __future__ import annotations

import time
from collections import deque
from typing import List, Optional

from znicz_tpu.core.mutable import Bool
from znicz_tpu.core.units import TrivialUnit, Unit


class StartPoint(TrivialUnit):
    pass


class EndPoint(TrivialUnit):
    def run(self) -> None:
        self.workflow.stopped.set(True)


class Repeater(TrivialUnit):
    """Loop-closing unit: opens its gate when ANY predecessor fired (the
    reference's plumbing.Repeater), so start_point and the tail of the GD
    chain can both feed it."""

    gate_any = True


class Workflow(Unit):
    """A Unit that owns a set of units and runs their control graph."""

    def __init__(self, workflow: Optional[Unit] = None,
                 name: Optional[str] = None, **kwargs) -> None:
        super().__init__(workflow=workflow, name=name, **kwargs)
        self.units: List[Unit] = []
        self.start_point = StartPoint(name="start_point")
        self.end_point = EndPoint(name="end_point")
        self.add_unit(self.start_point)
        self.add_unit(self.end_point)
        self.stopped = Bool(False)
        self.device = None
        self._run_time_started = 0.0

    # -- membership ----------------------------------------------------------

    def add_unit(self, unit: Unit) -> None:
        if unit not in self.units:
            # Uniquify the name: snapshots and attr-link debugging key units
            # by name, so two default-named All2AllTanh's must not collide.
            taken = {u.name for u in self.units}
            if unit.name in taken:
                i = 2
                while f"{unit.name}_{i}" in taken:
                    i += 1
                unit.name = f"{unit.name}_{i}"
            self.units.append(unit)
            unit.workflow = self

    def del_unit(self, unit: Unit) -> None:
        if unit in self.units:
            unit.unlink_all()
            self.units.remove(unit)
            unit.workflow = None

    def __iter__(self):
        return iter(self.units)

    def index_of(self, unit: Unit) -> int:
        return self.units.index(unit)

    # -- lifecycle -----------------------------------------------------------

    def initialize(self, device=None, **kwargs) -> None:
        """Initialize every unit.  Units whose initialize raises a documented
        ``ReInitRequired`` are retried after the rest (the reference iterated
        until attribute links resolved; one retry pass suffices here because
        links are lazy)."""
        super().initialize(**kwargs)
        if device is None:
            from znicz_tpu.backends import Device
            device = Device.auto()
        self.device = device
        pending = [u for u in self.units if not u.is_initialized]
        retry: List[tuple] = []
        for unit in pending:
            try:
                unit.initialize(device=device, **kwargs)
            except AttributeError as exc:
                retry.append((unit, exc))
        for unit, first_exc in retry:
            try:
                unit.initialize(device=device, **kwargs)
            except Exception as exc:
                # A genuinely broken unit fails both passes; surface the
                # first-pass error as the cause instead of hiding it.
                raise exc from first_exc

    def run(self) -> None:
        """Run the control graph until EndPoint fires (or nothing is ready)."""
        from znicz_tpu import telemetry

        if not self.is_initialized:
            self.initialize()
        self.stopped.set(False)
        for unit in self.units:
            unit.reset_links()
        tracer = telemetry.tracer()
        self._run_time_started = time.perf_counter()
        queue: deque[Unit] = deque([self.start_point])
        queued = {self.start_point}
        while queue and not self.stopped:
            unit = queue.popleft()
            queued.discard(unit)
            if bool(unit.gate_block):
                continue
            if not bool(unit.gate_skip):
                started = time.perf_counter()
                unit.run()
                elapsed = time.perf_counter() - started
                unit.run_time += elapsed
                unit.run_count += 1
                if tracer.enabled:
                    # reuse the timing above: one deque append per unit
                    # firing, no extra clock reads (ISSUE 5 span site)
                    tracer.add("unit", unit.name, started, elapsed)
            for target in unit.links_to:
                target.links_from[unit] = True
                fire = (any(target.links_from.values())
                        if getattr(target, "gate_any", False)
                        else all(target.links_from.values()))
                if fire and target not in queued:
                    # Dedup: a gate_any unit (Repeater) fed by two units that
                    # fire in the same wave must still run once per wave.
                    target.reset_links()
                    queue.append(target)
                    queued.add(target)
        self.run_time += time.perf_counter() - self._run_time_started

    def stop(self) -> None:
        self.stopped.set(True)
        for unit in self.units:
            if unit is not self:
                unit.stop()

    # -- observability -------------------------------------------------------

    def print_stats(self) -> str:
        """Per-unit wall-time table (the reference printed this at stop)."""
        total = sum(u.run_time for u in self.units) or 1e-12
        rows = sorted(self.units, key=lambda u: -u.run_time)
        lines = [f"{'unit':<32}{'runs':>8}{'time_s':>12}{'%':>8}"]
        for u in rows:
            if u.run_count == 0:
                continue
            lines.append(f"{u.name:<32}{u.run_count:>8}{u.run_time:>12.4f}"
                         f"{100.0 * u.run_time / total:>8.1f}")
        fused = getattr(self, "fused_stats", None)
        if fused and fused.get("wall_s"):
            line = (f"fused: {fused['train_steps']} train + "
                    f"{fused['eval_steps']} eval steps in "
                    f"{fused['wall_s']:.3f}s  "
                    f"({fused['steps_per_sec']} steps/s, "
                    f"{fused['img_per_sec']} img/s, "
                    f"last {fused['last_step_ms']} ms)")
            if fused.get("warm_steps"):
                line += (f"; warm (excl. compiles): "
                         f"{fused['warm_img_per_sec']} img/s over "
                         f"{fused['warm_steps']} steps")
            if fused.get("dispatches"):
                # what the device waits for: the host blocked on its
                # values, in the Decision, in the epoch-end hook
                line += (f"; {fused['dispatches']} dispatches, host "
                         f"{fused['sync_wait_s']:.3f}s in sync, "
                         f"{fused['decide_s']:.3f}s in decision, "
                         f"{fused['epoch_hook_s']:.3f}s in epoch hooks")
            lines.append(line)
        table = "\n".join(lines)
        self.info("unit timing:\n%s", table)
        return table

    def generate_graph(self) -> str:
        """Graphviz dot text of the control graph (reference:
        ``--workflow-graph``)."""
        lines = ["digraph workflow {", "  rankdir=TB;"]
        for unit in self.units:
            lines.append(f'  "{unit.name}" [shape=box];')
        for unit in self.units:
            for target in unit.links_to:
                lines.append(f'  "{unit.name}" -> "{target.name}";')
        lines.append("}")
        return "\n".join(lines)
