"""Dynamic-batching inference serving layer (ISSUE 4).

The training side of this repo got three PRs of optimization; this
package opens the INFERENCE workload the ROADMAP north star ("serves
heavy traffic from millions of users") requires: load a snapshot,
freeze params into an inference-only jitted forward, and serve
concurrent clients over ZeroMQ with the same wire-v3 zero-copy tensor
codec the master/slave stack speaks.

    serving/batcher.py   BucketLadder + DynamicBatcher — request
                         coalescing under (max_batch, max_delay_ms),
                         padding to a fixed bucket ladder (bounded jit
                         cache), bounded-queue backpressure
    serving/model.py     ModelRunner — frozen params, bucketed jit
                         cache with compile counters, donated
                         ping-pong stage/infer halves; mesh-native
                         (ISSUE 13): root.common.serving.mesh.* builds
                         a NamedSharding mesh, params replicate or
                         column-shard per FusedTrainer.param_sharding,
                         request batches split rows/dp over the data
                         axis directly from the host
    serving/frontend.py  InferenceServer — ZMQ ROUTER + codec + the
                         overlap compute loop; stats for web_status
    serving/client.py    InferenceClient — DEALER peer, pipelined
                         submits, resend-on-loss, req_id dedup,
                         per-endpoint breaker behind a balancer
    serving/balancer.py  ReplicaBalancer — fleet-grade front over N
                         replica processes (ISSUE 12): TTL'd heartbeat
                         membership, least-loaded dispatch,
                         exactly-once failover, hedged retries, canary
                         rollover with auto-rollback + healing

Overload safety + live operation (ISSUE 6): per-client token-bucket
rate limits and deficit-round-robin fair queueing in the batcher
(``root.common.serving.admission.*``), end-to-end deadline budgets
(client ships ``deadline_ms``, the frontend refuses expired work at
ingress/assemble/post-compute), a rolling-window circuit breaker in
the client, and zero-downtime snapshot rollover (``swap`` control
command / SIGHUP; every reply carries its snapshot ``gen``) with
``/healthz``/``/readyz`` on web_status.

Generation serving (ISSUE 16, paged in ISSUE 19): with
``root.common.serving.generate.enabled`` the frontend also speaks a
``generate`` request kind — prompt in, autoregressive tokens out.
Prompts prefill in fixed ``prefill_chunk`` token chunks into a
block-paged KV pool (full pages content-addressed and shared across
requests via the prefix cache, copy-on-write on divergence), then
O(cache) decode steps emit one token each with sampling fused
in-graph; decode steps from DIFFERENT requests coalesce every tick
(continuous batching) and finished sequences release their pages
mid-batch — the zero-recompile contract extended to the
(batch rung x page rung) prefill/decode families.

Config home: ``root.common.serving.{max_batch, max_delay_ms,
queue_bound, request_ttl_s}`` + ``root.common.serving.admission.*``
+ ``root.common.serving.mesh.*`` (pod-slice sharding, ISSUE 13)
+ ``root.common.serving.generate.*`` (ISSUE 16);
CLI: ``python -m znicz_tpu <workflow> --serve [BIND] --snapshot FILE``;
tests: ``tests/test_serving.py`` (see README "Serving" and "Serving
robustness").
"""

from .balancer import ReplicaBalancer                       # noqa: F401
from .batcher import (AdmissionPolicy, BucketLadder,        # noqa: F401
                      DynamicBatcher, GenerationScheduler, GenSeq,
                      Refusal, Request, TokenBucket)
from .client import (CircuitOpenError, InferenceClient,     # noqa: F401
                     InferenceError)
from .frontend import InferenceServer                       # noqa: F401
from .model import GenerationRunner, ModelRunner            # noqa: F401
