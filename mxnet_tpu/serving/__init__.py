"""``mxnet_tpu.serving`` — production inference serving.

The inference half of the north star (the role MXNet 1.x's C predict
API + model-server heritage played), built on the training stack's own
primitives:

- :class:`InferenceEngine` — AOT shape-bucket executables
  (``jax.jit(...).lower().compile()`` at deploy time, warmed through
  ``MXTPU_COMPILE_CACHE``), sealed with a hard no-retrace contract, fed
  by a continuous-batching scheduler (``SequenceBucketer`` selection +
  ``pad_batch`` fill, per-request deadlines, bounded-queue load shed);
- :class:`ModelRepository` — many named+versioned models on one
  device; staged load -> warmup -> atomic pointer flip (the PR-8
  checkpoint commit protocol in-memory), drain, instant rollback;
- serving SLOs on the observability registry (p50/p99 latency,
  batch-fill, queue depth, shed/timeout counters — scrapeable via
  ``observability.serve_metrics``; ``tools/telemetry_report.py`` has a
  Serving section);
- the autoregressive decode fast path — :class:`GenerationEngine`
  (token-level continuous batching: one sealed chunk-of-T decode
  executable with on-device sampling; requests join/leave between
  chunks) over :class:`PagedKVCache` (block-table paged K/V pool with
  free-list allocation and copy-on-fork shared prefixes), with
  :class:`TransformerDecoderLM` as the reference decode-capable net;
- the self-healing fleet layer — :class:`ServingFleet` /
  :class:`ReplicaSet` (replicas across processes/hosts behind one
  :class:`ReplicaRouter` with least-queue-depth dispatch, typed
  failover and optional hedging), :class:`SLOAutoscaler` (watchdog +
  SLO signals actuated through the PR-11 membership bus: grow, shrink,
  scale-to-zero with warm-pool restore, cooldown-exempt replacement of
  dead replicas), and the latched brownout degraded mode (``bulk``
  sheds before ``interactive`` before ``critical``).

Knobs: ``MXTPU_SERVE_MAX_BATCH`` / ``MXTPU_SERVE_MAX_WAIT_MS`` /
``MXTPU_SERVE_QUEUE`` + the ``MXTPU_FLEET_*`` family
(docs/env_vars.md); recipes: docs/serving.md, docs/robustness.md.
"""

from __future__ import annotations

from .batcher import ContinuousBatcher, ServeFuture  # noqa: F401
from .engine import (  # noqa: F401
    InferenceEngine,
    serve_max_batch,
    serve_max_wait_ms,
    serve_queue_cap,
)
from .errors import (  # noqa: F401
    BrownoutShed,
    EngineClosed,
    KVCacheOOM,
    ReplicaDead,
    ReplicaLost,
    RequestCancelled,
    RequestTimeout,
    RequestTooLarge,
    RetraceForbidden,
    ServerOverloaded,
    ServingError,
    StagedLoadError,
)
from .kvcache import (  # noqa: F401
    BlockTable,
    PagedKVCache,
    SequenceCache,
    StateStore,
    kvcache_block_size,
    kvcache_blocks,
)
from .decoder import TransformerDecoderLM  # noqa: F401
from .generation import (  # noqa: F401
    GenerateFuture,
    GenerationEngine,
    decode_chunk,
    decode_max_new,
    decode_slots,
    sample_tokens,
)
from .repository import ModelRepository  # noqa: F401
from .replica import LocalReplica, ProcessReplica  # noqa: F401
from .router import (  # noqa: F401
    FleetFuture,
    ReplicaRouter,
    federation_depth_feed,
    fleet_hedge_ms,
    fleet_retries,
)
from .fleet import (  # noqa: F401
    PRIORITIES,
    ReplicaSet,
    ServingFleet,
    fleet_brownout_enter,
    fleet_brownout_exit,
    fleet_brownout_hold_s,
    fleet_heartbeat_s,
    fleet_max_replicas,
    fleet_min_replicas,
    fleet_replicas,
    fleet_suspect_misses,
)
from .autoscaler import SLOAutoscaler, fleet_cooldown_s, fleet_slo_p99_ms  # noqa: F401
