"""Launcher + CLI (rebuild of ``veles/launcher.py`` / ``veles/__main__.py``,
SURVEY.md §3.1).

Reference surface preserved::

    python -m znicz_tpu <workflow.py|module> [config.py]
        [root.path.key=value ...] [--snapshot FILE] [--backend cpu|tpu]
        [--workflow-graph FILE.dot] [--list]

A workflow script is any python file/module exposing ``run(snapshot=...,
device=...) -> workflow`` (all the bundled samples do); a config file is any
python file mutating ``znicz_tpu.core.config.root`` (applied before the
workflow module loads, then CLI dotted overrides on top — reference
precedence).

Distribution: the PRIMARY mode is SPMD inside the jitted step (SURVEY.md
§2.4) — no flags needed.  The reference's ``--master``/``--slave`` CLI
surface is preserved for the asynchronous parameter-server mode
(server.py/client.py): ``--master [bind]`` builds the workflow and serves
jobs instead of training locally; ``--slave endpoint`` builds the local
replica and works for that master.
"""

from __future__ import annotations

import argparse
import importlib
import importlib.util
import os
import signal
import sys
from typing import Dict, List, Optional

from znicz_tpu.core.config import apply_overrides, root
from znicz_tpu.core.logger import setup_logging

SAMPLES = ("mnist", "cifar", "mnist_ae", "kohonen", "alexnet", "wine",
           "yale_faces", "kanji", "video_ae", "charlm")


def _load_module(spec: str, tag: str):
    if os.path.exists(spec):
        mod_spec = importlib.util.spec_from_file_location(tag, spec)
        mod = importlib.util.module_from_spec(mod_spec)
        sys.modules[tag] = mod
        mod_spec.loader.exec_module(mod)
        return mod
    return importlib.import_module(spec)


class Launcher:
    def __init__(self, argv: Optional[List[str]] = None):
        parser = argparse.ArgumentParser(
            prog="znicz_tpu",
            description="TPU-native VELES/Znicz workflow launcher")
        parser.add_argument("workflow", nargs="?",
                            help="workflow .py file, module path, or bundled "
                                 f"sample name ({', '.join(SAMPLES)})")
        parser.add_argument("config", nargs="?",
                            help="optional config .py file (mutates root)")
        parser.add_argument("overrides", nargs="*",
                            help="dotted overrides: root.a.b=value")
        parser.add_argument("--snapshot", default="",
                            help="resume from a snapshot file")
        parser.add_argument("--backend", default=None,
                            help="jax platform: tpu/cpu (default auto)")
        parser.add_argument("--seed", type=int, default=None)
        parser.add_argument("--workflow-graph", default="",
                            help="write the control graph as graphviz dot")
        parser.add_argument("--profile", default="",
                            help="capture a jax.profiler trace of the whole "
                                 "run into this directory")
        parser.add_argument("--profile-dir", default="", metavar="DIR",
                            help="programmatic jax profiler capture "
                                 "(start_trace/stop_trace) into DIR; the "
                                 "program's spans are in it as znicz:* "
                                 "annotations, every fused dispatch a "
                                 "jax.profiler.StepTraceAnnotation "
                                 "(supersedes --profile when both are "
                                 "given)")
        parser.add_argument("--fused", action="store_true",
                            help="train with the fused SPMD fast path "
                                 "(one jitted scan step) instead of the "
                                 "unit-at-a-time engine")
        parser.add_argument("--master", nargs="?", const="tcp://*:5570",
                            default=None, metavar="BIND",
                            help="serve this workflow as the async "
                                 "parameter-server master instead of "
                                 "training locally (default bind "
                                 "tcp://*:5570)")
        parser.add_argument("--slave", default=None, metavar="ENDPOINT",
                            help="work for the master at ENDPOINT "
                                 "(e.g. tcp://host:5570)")
        parser.add_argument("--relay", default=None,
                            metavar="UPSTREAM[:BIND]",
                            help="run an aggregation-tree relay node "
                                 "(ISSUE 10): accept slaves/relays at "
                                 "BIND (default tcp://*:5571; a bare "
                                 "port means tcp://*:PORT), validate + "
                                 "sum-reduce their deltas and forward "
                                 "ONE combined update to UPSTREAM.  "
                                 "Needs no workflow argument")
        parser.add_argument("--tree-fanout", type=int, default=None,
                            metavar="N",
                            help="children per relay "
                                 "(root.common.engine.tree_fanout, "
                                 "default 2): the flush threshold and "
                                 "job-batch amplification factor")
        parser.add_argument("--min-slaves", type=int, default=None,
                            metavar="N",
                            help="elastic quorum gate for the master "
                                 "role (root.common.engine.min_slaves): "
                                 "below N live members (direct slaves + "
                                 "leaves reported by relays) dispatch "
                                 "pauses and readiness reports degraded")
        parser.add_argument("--staleness-bound", type=int, default=None,
                            metavar="S",
                            help="bounded-staleness apply "
                                 "(root.common.engine.staleness_bound): "
                                 "refuse-and-requeue deltas staler than "
                                 "S applies; 0 = unbounded")
        parser.add_argument("--plan-tree", type=int, default=None,
                            metavar="N_SLAVES",
                            help="print the relay-tree plan (tiers, "
                                 "endpoints, per-slave assignments) "
                                 "for N_SLAVES at --tree-fanout and "
                                 "exit")
        parser.add_argument("--serve", nargs="?", const="tcp://*:5580",
                            default=None, metavar="BIND",
                            help="serve this workflow's forward as a "
                                 "dynamic-batching inference service "
                                 "instead of training (load params with "
                                 "--snapshot; default bind tcp://*:5580; "
                                 "knobs: root.common.serving.max_batch/"
                                 "max_delay_ms/queue_bound)")
        parser.add_argument("--mesh-data", type=int, default=None,
                            metavar="N",
                            help="data-axis size of the pod-slice mesh: "
                                 "with --serve, root.common.serving."
                                 "mesh.data (each request batch splits "
                                 "into N row shards, ISSUE 13); with "
                                 "--slave, root.common.engine.mesh.data "
                                 "+ the train_shard gate (grads psum "
                                 "over ICI inside the slice, ISSUE 18). "
                                 "With --backend cpu, N x --mesh-model "
                                 "virtual devices are provisioned")
        parser.add_argument("--mesh-model", type=int, default=None,
                            metavar="N",
                            help="model-axis size of the pod-slice mesh "
                                 "(serving.mesh.model with --serve, "
                                 "engine.mesh.model with --slave) — "
                                 "wide FC layers column-shard over N "
                                 "devices")
        parser.add_argument("--generate", action="store_true",
                            help="with --serve: also speak the "
                                 "'generate' request kind — paged-KV "
                                 "autoregressive generation with "
                                 "prefix reuse, chunked prefill and "
                                 "fused sampling (root.common.serving."
                                 "generate.enabled; knobs: generate."
                                 "page_size/num_pages/prefill_chunk/"
                                 "prefix_cache/on_device_sampling)")
        parser.add_argument("--announce", default=None,
                            metavar="BALANCER",
                            help="with --serve: heartbeat this replica "
                                 "into the balancer at BALANCER "
                                 "(ISSUE 12) — readiness, queue depth "
                                 "and per-bucket p99 piggyback on "
                                 "every beat")
        parser.add_argument("--replica-id", default=None, metavar="ID",
                            help="with --serve: stable replica identity "
                                 "stamped on every reply (default: a "
                                 "fresh uuid per process)")
        parser.add_argument("--balance", nargs="?", const="tcp://*:5590",
                            default=None, metavar="BIND",
                            help="run the replica-fleet balancer "
                                 "(ISSUE 12) at BIND (default "
                                 "tcp://*:5590): health-checked "
                                 "least-loaded dispatch over the "
                                 "replicas that --announce into it, "
                                 "exactly-once failover, hedged "
                                 "retries, canary rollover with "
                                 "auto-rollback.  Needs no workflow "
                                 "argument; knobs: "
                                 "root.common.serving.balance.*")
        parser.add_argument("--replicas", default="", metavar="EP[,EP]",
                            help="with --balance: static replica "
                                 "endpoints to pre-connect (membership "
                                 "still needs their heartbeats)")
        parser.add_argument("--aot-cache", nargs="?", const="auto",
                            default=None, metavar="DIR",
                            help="with --serve: arm the AOT executable "
                                 "cache (root.common.serving.aot_cache) "
                                 "— warmed executables are serialized "
                                 "next to the snapshot (or into DIR) "
                                 "and a restarted replica LOADS its "
                                 "family instead of compiling it "
                                 "(zero-cold-start boots)")
        parser.add_argument("--autoscale-max", type=int, default=None,
                            metavar="N",
                            help="with --balance and --spawn-cmd: arm "
                                 "the autoscaler — spawn/retire replica "
                                 "processes against the load band, "
                                 "never past N replicas and never "
                                 "below --min-replicas")
        parser.add_argument("--spawn-cmd", default="", metavar="CMD",
                            help="with --autoscale-max: shell command "
                                 "that boots ONE replica announcing to "
                                 "this balancer; '{announce}' and "
                                 "'{replica_id}' are substituted (e.g. "
                                 "\"python -m znicz_tpu mnist --serve "
                                 "'tcp://127.0.0.1:*' --snapshot s.pkl.gz "
                                 "--aot-cache --announce {announce} "
                                 "--replica-id {replica_id}\")")
        parser.add_argument("--min-replicas", type=int, default=None,
                            metavar="N",
                            help="with --balance: readiness quorum "
                                 "(root.common.serving.balance."
                                 "min_replicas) — the aggregate "
                                 "/readyz 503s below N ready replicas")
        parser.add_argument("--master-resume", default="", metavar="FILE",
                            help="master crash-resume file: restore "
                                 "training state from FILE when it "
                                 "exists and keep it updated while "
                                 "serving (implies --master)")
        parser.add_argument("--fitness", action="store_true",
                            help="print a final JSON line with the run's "
                                 "fitness (genetics subprocess evaluation)")
        parser.add_argument("--list", action="store_true",
                            help="list bundled samples")
        # intermixed: dotted overrides may appear before or after flags
        # (the genetics evaluator appends chromosome overrides after the
        # caller's flags)
        self.args = parser.parse_intermixed_args(argv)
        #: the workflow the run built (None for the workflow-less roles)
        self.workflow = None

    def run(self) -> int:
        setup_logging()
        args = self.args
        if args.tree_fanout is not None:
            root.common.engine.tree_fanout = int(args.tree_fanout)
        if args.min_slaves is not None:
            root.common.engine.min_slaves = int(args.min_slaves)
        if args.staleness_bound is not None:
            root.common.engine.staleness_bound = int(args.staleness_bound)
        if args.min_replicas is not None:
            root.common.serving.balance.min_replicas = \
                int(args.min_replicas)
        if args.aot_cache is not None:
            root.common.serving.aot_cache.enabled = True
            if args.aot_cache != "auto":
                root.common.serving.aot_cache.dir = str(args.aot_cache)
        if args.generate:
            root.common.serving.generate.enabled = True
        if args.mesh_data is not None or args.mesh_model is not None:
            if args.slave is not None:
                # a pod-sliced TRAINING leaf (ISSUE 18): the mesh flags
                # target the engine tree and flip the train_shard gate
                root.common.engine.train_shard = True
                if args.mesh_data is not None:
                    root.common.engine.mesh.data = int(args.mesh_data)
                if args.mesh_model is not None:
                    root.common.engine.mesh.model = int(args.mesh_model)
            else:
                if args.mesh_data is not None:
                    root.common.serving.mesh.data = int(args.mesh_data)
                if args.mesh_model is not None:
                    root.common.serving.mesh.model = \
                        int(args.mesh_model)
        if args.plan_tree is not None:
            return self._plan_tree(args)
        if args.balance is not None:
            if args.master is not None or args.slave is not None \
                    or args.serve is not None or args.relay is not None \
                    or args.master_resume:
                print("error: --balance is mutually exclusive with the "
                      "master/slave/serve/relay roles", file=sys.stderr)
                return 2
            return self._balance(args)
        if args.relay is not None:
            if args.master is not None or args.slave is not None \
                    or args.serve is not None or args.master_resume:
                print("error: --relay is mutually exclusive with the "
                      "master/slave/serve roles", file=sys.stderr)
                return 2
            return self._relay(args)
        if args.list or not args.workflow:
            print("bundled samples:", ", ".join(SAMPLES))
            return 0
        # argparse can't distinguish "config.py" from the first dotted
        # override positionally — reclassify by the "=" marker
        if args.config and "=" in args.config:
            args.overrides.insert(0, args.config)
            args.config = None
        if args.backend:
            root.common.engine.backend = args.backend
            if args.backend == "cpu":
                # must happen BEFORE the first jax backend init.  A
                # serving mesh on a CPU host needs dp x mp VIRTUAL
                # devices (ISSUE 13)
                from znicz_tpu.virtdev import provision_cpu_devices

                provision_cpu_devices(
                    max(1, (args.mesh_data or 1)
                        * (args.mesh_model or 1)), verify=False)
        if args.fused:
            root.common.engine.fused = True
        if args.master is not None and args.slave is not None:
            print("error: --master and --slave are mutually exclusive",
                  file=sys.stderr)
            return 2
        if args.serve is not None and (args.master is not None
                                       or args.slave is not None
                                       or args.master_resume):
            print("error: --serve is mutually exclusive with the "
                  "master/slave training roles", file=sys.stderr)
            return 2
        if args.master_resume:
            if args.slave is not None:
                print("error: --master-resume applies to the master role",
                      file=sys.stderr)
                return 2
            root.common.engine.master_resume = args.master_resume
            if args.master is None:
                args.master = "tcp://*:5570"      # implies --master
        if args.master is not None:
            root.common.engine.mode = "master"
            root.common.engine.master_bind = args.master
        elif args.slave is not None:
            root.common.engine.mode = "slave"
            root.common.engine.slave_endpoint = args.slave
        if args.seed is not None:
            from znicz_tpu.core import prng

            prng.seed_all(args.seed)
        if args.config:
            _load_module(args.config, "znicz_tpu._user_config")
        if args.overrides:
            apply_overrides(root, args.overrides)
        # a mesh may also arrive via the config file or dotted overrides
        # (not just the --mesh-* flags read above): now that both are
        # applied, re-raise the CPU virtual-device count if the
        # configured mesh needs more — still before the first jax
        # backend init, and provision only ever raises the count
        if args.backend == "cpu":
            need = 1
            if args.serve is not None:
                mc = root.common.serving.mesh
                need = int(mc.get("data", 1)) * int(mc.get("model", 1))
            elif root.common.engine.get("train_shard", False):
                # a pod-sliced training leaf (ISSUE 18)
                mc = root.common.engine.mesh
                need = int(mc.get("data", 1)) * int(mc.get("model", 1))
            if need > 1:
                from znicz_tpu.virtdev import provision_cpu_devices

                provision_cpu_devices(need, verify=False)
        # XLA scheduler flags must land in the env BEFORE the workflow
        # module's first jax backend init (ISSUE 7: the latency-hiding
        # scheduler is the compiler half of ingest/compute overlap;
        # root.common.engine.xla_latency_hiding, default off)
        from znicz_tpu.backends import (configure_compile_cache,
                                        configure_xla_flags)

        configure_xla_flags()
        configure_compile_cache()
        spec = args.workflow
        if spec in SAMPLES:
            spec = f"znicz_tpu.samples.{spec}"
        mod = _load_module(spec, "znicz_tpu._user_workflow")
        if args.serve is not None:
            return self._serve(mod, spec, args)
        if not hasattr(mod, "run"):
            print(f"error: {spec} does not expose run()", file=sys.stderr)
            return 2
        import inspect

        kwargs = {}
        sig = inspect.signature(mod.run)
        if "snapshot" in sig.parameters and args.snapshot:
            kwargs["snapshot"] = args.snapshot
        if args.profile_dir:
            # programmatic capture:
            # the trace holds the program's own spans as ``znicz:*``
            # annotations (telemetry/trace.py) — one StepTraceAnnotation
            # per fused dispatch — with nothing to arm here
            import jax

            jax.profiler.start_trace(args.profile_dir)
            try:
                wf = mod.run(**kwargs)
            finally:
                jax.profiler.stop_trace()
                print(f"profiler trace -> {args.profile_dir}/")
        elif args.profile:
            import jax

            with jax.profiler.trace(args.profile):
                wf = mod.run(**kwargs)
            print(f"profiler trace -> {args.profile}/")
        else:
            wf = mod.run(**kwargs)
        self.workflow = wf
        if args.workflow_graph and wf is not None:
            with open(args.workflow_graph, "w") as f:
                f.write(wf.generate_graph())
            print(f"workflow graph -> {args.workflow_graph}")
        if args.fitness:
            import json

            fit = None
            decision = getattr(wf, "decision", None)
            if decision is not None:
                fit = getattr(decision, "best_metric", None)
                if fit is None and getattr(decision, "epoch_qerror", None):
                    fit = decision.epoch_qerror[-1]
            import math

            if fit is None or not math.isfinite(float(fit)):
                # inf best_metric means no epoch ever improved — emitting
                # json 'Infinity' would be non-RFC JSON, so report no fitness.
                print("error: workflow exposes no finite fitness "
                      "(decision.best_metric / epoch_qerror)",
                      file=sys.stderr)
                return 3
            print(json.dumps({"genetics_fitness": float(fit)}), flush=True)
        return 0

    def _plan_tree(self, args) -> int:
        """``--plan-tree N``: print the relay tiers a fleet of N slaves
        needs at the configured fanout, as one JSON document — concrete
        ``--relay`` specs (top tier first, so starting them in order
        brings the tree up parents-before-children) plus the endpoint
        each slave should dial."""
        import json

        from znicz_tpu.parallel.relay import plan_tree

        master = (args.master
                  or str(root.common.engine.get("master_bind",
                                                "tcp://*:5570")))
        master = master.replace("*", "127.0.0.1")
        try:
            plan = plan_tree(
                int(args.plan_tree),
                int(root.common.engine.get("tree_fanout", 2)), master)
        except ValueError as exc:
            print(f"error: {exc}", file=sys.stderr)
            return 2
        plan["master"] = master
        plan["relay_args"] = [f"{r['upstream']}:{r['bind']}"
                              for r in plan["relays"]]
        print(json.dumps(plan, indent=2))
        return 0

    def _balance(self, args) -> int:
        """``--balance [BIND] --replicas ep,...``: run the replica
        balancer until interrupted (or ``root.common.serving
        .max_requests`` answers, for tests).  No workflow is built —
        the balancer moves frames, never arrays."""
        from znicz_tpu.serving import ReplicaBalancer

        # --balance needs no workflow, so dotted overrides land in the
        # workflow/config positional slots — reclassify and apply them
        # here (the main flow applies overrides after role dispatch)
        overrides = [o for o in ([args.workflow, args.config]
                                 + list(args.overrides))
                     if o and "=" in o]
        stray = [o for o in (args.workflow, args.config)
                 if o and "=" not in o]
        if stray:
            print(f"error: --balance takes no workflow argument "
                  f"(got {stray})", file=sys.stderr)
            return 2
        if overrides:
            apply_overrides(root, overrides)
        replicas = tuple(ep.strip() for ep in args.replicas.split(",")
                         if ep.strip())
        max_requests = root.common.serving.get("max_requests", None)
        balancer = ReplicaBalancer(
            bind=args.balance, replicas=replicas,
            max_requests=None if max_requests is None
            else int(max_requests))
        status = None
        web_port = root.common.serving.get("web_port", None)
        if web_port is not None:
            from znicz_tpu.web_status import WebStatus

            status = WebStatus(port=int(web_port)).start()
            status.register_balancer(balancer)
            print(f"fleet dashboard -> http://127.0.0.1:{status.port}/")
        balancer.start()
        static = (", ".join(replicas) if replicas
                  else "none — awaiting --announce heartbeats")
        print(f"balancing at {balancer.endpoint} (static replicas: "
              f"{static}; quorum {balancer.min_replicas})", flush=True)
        # autoscaler (ISSUE 17): spawn/retire replica PROCESSES via
        # --spawn-cmd against the load band; retire only reaches
        # processes this balancer spawned (the initial fleet is the
        # operator's)
        procs: Dict = {}
        if args.autoscale_max is not None and args.spawn_cmd:
            import shlex
            import subprocess
            import threading

            seq = {"n": 0}
            plock = threading.Lock()

            def _spawn() -> None:
                with plock:
                    seq["n"] += 1
                    rid = f"scale-{seq['n']}"
                cmd = args.spawn_cmd.format(announce=balancer.endpoint,
                                            replica_id=rid)
                p = subprocess.Popen(shlex.split(cmd))
                with plock:
                    procs[rid] = p
                print(f"autoscale: spawned {rid} (pid {p.pid})",
                      flush=True)

            def _retire(replica_id: str) -> None:
                with plock:
                    p = procs.pop(replica_id, None)
                if p is None:
                    print(f"autoscale: {replica_id} was not spawned "
                          f"here — draining only, not killing",
                          flush=True)
                    return
                p.terminate()
                print(f"autoscale: retired {replica_id}", flush=True)

            balancer.enable_autoscale(
                _spawn, _retire,
                autoscale_max=int(args.autoscale_max))
            print(f"autoscaling up to {int(args.autoscale_max)} "
                  f"replicas via: {args.spawn_cmd}", flush=True)
        try:
            while balancer.alive():
                if balancer.max_requests is not None and \
                        balancer.replied + balancer.refused \
                        >= balancer.max_requests:
                    break
                import time

                time.sleep(0.2)
        except KeyboardInterrupt:
            pass
        finally:
            balancer.stop()
            for p in procs.values():    # spawned replicas die with us
                p.terminate()
            if status is not None:
                status.stop()
        return 0

    def _relay(self, args) -> int:
        """``--relay UPSTREAM[:BIND]``: run one relay node until its
        upstream reports training done (or Ctrl-C).  No workflow is
        built — the relay validates children by passing the first
        handshake upstream."""
        from znicz_tpu.parallel.relay import Relay, parse_relay_spec

        try:
            upstream, bind = parse_relay_spec(args.relay)
        except ValueError as exc:
            print(f"error: {exc}", file=sys.stderr)
            return 2
        relay = Relay(upstream, bind)
        print(f"relay {relay.relay_id}: children at {bind} -> "
              f"upstream {upstream} (fanout {relay.fanout}, "
              f"wire {relay.wire_dtype})", flush=True)
        try:
            relay.serve()
        except KeyboardInterrupt:
            pass
        return 0

    def _serve(self, mod, spec: str, args) -> int:
        """``--serve``: build the module's workflow WITHOUT training it
        (the samples' ``run()`` trains), load ``--snapshot`` through the
        snapshotter's inference-load path, and serve the frozen forward
        as a dynamic-batching service until interrupted (or until
        ``root.common.serving.max_requests`` requests, for tests)."""
        from znicz_tpu.core.workflow import Workflow

        classes = [v for v in vars(mod).values()
                   if isinstance(v, type) and issubclass(v, Workflow)
                   and v is not Workflow
                   and v.__module__ == mod.__name__]
        if len(classes) != 1:
            print(f"error: --serve needs exactly one Workflow subclass "
                  f"in {spec}; found "
                  f"{[c.__name__ for c in classes] or 'none'}",
                  file=sys.stderr)
            return 2
        wf = self.workflow = classes[0]()
        wf.initialize(device=None)

        from znicz_tpu.serving import InferenceServer

        max_requests = root.common.serving.get("max_requests", None)
        server = InferenceServer(
            wf, bind=args.serve, snapshot=args.snapshot,
            max_requests=None if max_requests is None
            else int(max_requests),
            announce=args.announce, replica_id=args.replica_id)
        status = None
        web_port = root.common.serving.get("web_port", None)
        if web_port is not None:
            from znicz_tpu.web_status import WebStatus

            status = WebStatus(port=int(web_port)).start()
            status.register(wf)
            status.register_inference(server)
            print(f"status dashboard -> http://127.0.0.1:{status.port}/")
        server.start()
        print(f"serving {wf.name} at {server.endpoint} "
              f"(snapshot: {args.snapshot or 'fresh init'})", flush=True)
        # zero-downtime rollover on SIGHUP (ISSUE 6): re-load --snapshot
        # (the conventional "new weights land at the same path" flow)
        # and flip generations without dropping a request.  Signals can
        # only be wired from the main thread (tests drive main() from a
        # worker thread — they use the wire `swap` command instead).
        import threading

        if args.snapshot and hasattr(signal, "SIGHUP") \
                and threading.current_thread() is threading.main_thread():
            def _rollover(signum, frame):
                try:
                    server.swap_async(args.snapshot)
                    print(f"SIGHUP: snapshot rollover from "
                          f"{args.snapshot} started", flush=True)
                except RuntimeError as exc:    # overlapping swap
                    print(f"SIGHUP ignored: {exc}", flush=True)

            signal.signal(signal.SIGHUP, _rollover)
            print("SIGHUP triggers a zero-downtime snapshot rollover",
                  flush=True)
        try:
            server.join()
        except KeyboardInterrupt:
            pass
        finally:
            server.stop()
            if status is not None:
                status.stop()
        return 0


def main(argv: Optional[List[str]] = None) -> int:
    return Launcher(argv).run()
