"""Streaming model serving.

The analog of Cluster Serving (ref: zoo/.../serving -- Flink job reading
Redis Streams, micro-batching into an InferenceModel, akka-http frontend;
SURVEY.md sections 2.1/3.5). The TPU-native redesign replaces
Flink TM + Redis + akka with: a dependency-free durable queue (directory
backend, atomic claim via rename; or in-memory for single-process),
a micro-batcher with bounded backpressure, a serving worker around
``InferenceModel``, and a stdlib HTTP frontend with /predict + /metrics.
Resilience (supervised restarts, circuit breaker, deadlines, load
shedding) lives in ``resilience``; the deterministic fault-injection
harness that proves it lives in ``chaos``. ``fleet`` scales all of it
horizontally: N replica launcher processes sharding one consumer-group
stream (``redis_adapter`` stream mode) behind a health-checking HTTP
router, with drain-based rolling restarts and a metrics-driven
autoscaler. ``generation`` adds the token-streaming data plane
(ISSUE-10): prefill/decode split over a paged KV cache
(``inference.kv_cache``), slot-based continuous batching, and chunked
``POST /generate`` streams -- same supervisor/drain/chaos/fleet seams
as the predict worker. The wire vocabulary -- reserved blob keys and
structured error prefixes -- has ONE declaring module, ``protocol``
(lint-enforced by zoolint's protocol family).
"""

from analytics_zoo_tpu.serving.queues import (  # noqa: F401
    InputQueue,
    OutputQueue,
    DirQueue,
    MemQueue,
)
from analytics_zoo_tpu.serving.batcher import AdaptiveBatcher  # noqa: F401
from analytics_zoo_tpu.serving.worker import ServingWorker  # noqa: F401
from analytics_zoo_tpu.serving.generation import (  # noqa: F401
    ContinuousBatcher,
    DecodeEngine,
    GenerationWorker,
    GenModelConfig,
    TinyGenLM,
)
from analytics_zoo_tpu.serving.launcher import (  # noqa: F401
    ServingApp,
    launch,
    launch_from_yaml,
)
from analytics_zoo_tpu.serving.timer import Timer  # noqa: F401
from analytics_zoo_tpu.serving.http_frontend import (  # noqa: F401
    HttpFrontend,
)
from analytics_zoo_tpu.serving.redis_adapter import (  # noqa: F401
    RedisFrontend,
    RedisStreamQueue,
    StreamStore,
)
from analytics_zoo_tpu.serving.fleet import (  # noqa: F401
    Autoscaler,
    FleetController,
    FleetRouter,
)
from analytics_zoo_tpu.serving.resilience import (  # noqa: F401
    CircuitBreaker,
    RequestLedger,
    Supervisor,
)
from analytics_zoo_tpu.serving.chaos import (  # noqa: F401
    ChaosInjector,
    parse_spec,
)
from analytics_zoo_tpu.serving.protocol import (  # noqa: F401
    ERROR_PREFIXES,
    WIRE_KEYS,
    error_status,
)
