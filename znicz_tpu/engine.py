"""Engine selection for a built workflow: the unit-at-a-time graph engine
(reference execution semantics, ``Workflow.run``) vs the fused SPMD fast
path (``znicz_tpu/parallel/fused.py``), chosen by
``root.common.engine.fused`` — the launcher's ``--fused`` flag.

The fused path requires the StandardWorkflow graph shape (forwards / gds /
loader / decision) and no tied weights; anything else (Kohonen, RBM,
hand-wired graphs) falls back to the unit engine automatically.
"""

from __future__ import annotations

from znicz_tpu.core.config import root


def wants_fused() -> bool:
    return bool(root.common.engine.get("fused", False))


def _fused_capable(workflow) -> bool:
    """--fused applies: requested AND the graph has the StandardWorkflow
    shape the fused engine needs (one predicate for the local and slave
    branches — they must never disagree)."""
    return wants_fused() and all(
        getattr(workflow, a, None) is not None
        for a in ("forwards", "gds", "loader", "decision"))


def _check_distributable(workflow, mode: str) -> None:
    missing = [a for a in ("forwards", "loader", "decision")
               if getattr(workflow, a, None) is None]
    if missing:
        raise ValueError(
            f"--{mode} needs a StandardWorkflow-shaped graph; "
            f"{workflow.name} lacks {missing}")


def train(workflow) -> None:
    """Train ``workflow`` with the configured engine/mode.

    ``root.common.engine.mode`` (the launcher's ``--master``/``--slave``)
    switches to the asynchronous parameter-server roles — the reference's
    CLI distribution surface (SURVEY §3.1/§3.4) — instead of local
    training."""
    mode = root.common.engine.get("mode", "")
    if mode == "master":
        from znicz_tpu.server import Server

        _check_distributable(workflow, mode)
        # --master-resume: restore mid-training state when the file
        # exists and keep it updated while serving (crash-resume)
        Server(workflow,
               endpoint=root.common.engine.get("master_bind",
                                               "tcp://*:5570"),
               resume_path=root.common.engine.get("master_resume",
                                                  "")).serve()
        return
    if mode == "slave":
        from znicz_tpu.client import Client, FusedClient

        _check_distributable(workflow, mode)
        endpoint = root.common.engine.get("slave_endpoint")
        client = None
        # --fused --slave: jobs run as FusedTrainer scan dispatches (one
        # compiled segment per job) instead of unit-graph laps; protocol
        # unchanged (VERDICT r4 item 5).  Graphs the fused engine cannot
        # run fall back to the unit Client, mirroring the local --fused
        # fallback below.  Catch ONLY the dedicated refusal types
        # (FusedUnsupportedError covers the tied-weights refusal and the
        # host-staged-loader subclass FusedStagingUnsupportedError) — a
        # bare ValueError is a real config error and must propagate, not
        # silently demote the slave to the slow unit engine.
        if _fused_capable(workflow):
            from znicz_tpu.parallel.fused import FusedUnsupportedError

            try:
                client = FusedClient(workflow, endpoint=endpoint)
            except FusedUnsupportedError as exc:
                import logging

                logging.getLogger("znicz").warning(
                    "fused slave unavailable (%s); falling back to the "
                    "unit-engine slave", exc)
        if client is None:
            client = Client(workflow, endpoint=endpoint)
        client.run()
        return
    if _fused_capable(workflow):
        from znicz_tpu.parallel.fused import FusedTrainer, \
            FusedUnsupportedError
        from znicz_tpu.parallel.mesh import train_mesh_from_config

        try:
            # root.common.engine.train_shard + engine.mesh.* place the
            # local run over a pod slice exactly as they do a fused
            # slave's (None, the single-device path, when gated off)
            trainer = FusedTrainer(workflow,
                                   mesh=train_mesh_from_config())
        except FusedUnsupportedError as exc:    # e.g. tied weights
            workflow.warning(
                "--fused requested but the fused path cannot run this "
                "graph (%s); falling back to the unit engine", exc)
            workflow.run()
            return
        trainer.run()
    else:
        workflow.run()
