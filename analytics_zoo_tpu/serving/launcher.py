"""Config-driven serving launcher.

The analog of the reference's YAML-configured serving deployment
(ref: scripts/cluster-serving/config.yaml parsed by
zoo/.../serving/utils/ClusterServingHelper.scala; job lifecycle in
ClusterServingManager). One YAML describes the model, the queue, the
batching params and the HTTP frontend; ``launch(config)`` (or
``python -m analytics_zoo_tpu.serving.launcher -c config.yaml``)
assembles InferenceModel + ServingWorker + HttpFrontend and runs until
stopped.

Config schema (defaults in parentheses)::

    model:
      path: /path/to/saved_zoo_model     # ZooModel.save_model dir
      encrypted: false                   # load_encrypted_zoo
      secret: null                       #   its AES secret
    data:
      queue: memory | dir | tcp://host:port | redis://host:port (memory)
      path: null                         # dir-queue directory, or
                                         # host:port when queue: tcp/redis
      maxlen: 10000
      group: serving                     # redis: consumer-group name --
      consumer: null                     #   N replicas sharing a group
                                         #   shard the stream; consumer
                                         #   names this member's claims
      stream: serving_stream             # redis: request stream
      result_stream: result_stream       # redis: worker default output
    params:
      batch_size: 8                      # base micro-batch cap (core_number)
      timeout_ms: 5.0                    # max linger per batch
      min_timeout_ms: 1.0                # adaptive linger floor (shallow queue)
      max_batch_size: 0                  # backlog growth cap (0 = 4x batch_size
                                         # bucket); growth stays on the ladder
      top_n: null                        # classes/scores of top-N
      pipeline_depth: 2                  # in-flight predict batches
                                         # (1 disables overlap)
      warm_batch_sizes: [1, 8]           # pre-compiled buckets (uses the
                                         # model's example input)
    http:
      enabled: true
      host: 127.0.0.1
      port: 0                            # 0 = pick a free port
      certfile: null                     # both set -> HTTPS (ref:
      keyfile: null                      #   FrontEndApp https options)
    generation:                          # token streaming (ISSUE-10);
      enabled: true                      #   presence enables it. With
      model:                             #   no model: block the app
        vocab: 64                        #   serves generation ONLY.
        dim: 32                          # GenModelConfig fields (the
        heads: 2                         #   seeded builtin LM)
        head_dim: 16
        layers: 2
        seed: 0
      stream: generation_stream          # brokered request stream
      slots: null                        # null = zoo.generation.*
      page_size: null                    #   defaults; per-launch
      num_pages: null                    #   overrides otherwise
      max_len: null
      max_tokens: null                   # default new-token budget
      eos: null                          # default stop token id
      stream_chunk_tokens: null          # tokens per streamed chunk
      role: unified                      # unified | prefill | decode
                                         #   (ISSUE-20): prefill admits
                                         #   + prefills, hands streams
                                         #   to the decode pool over
                                         #   the broker handoff stream;
                                         #   decode consumes ONLY that
                                         #   stream. Non-unified roles
                                         #   need data.queue redis://
      handoff_stream: generation_handoff_stream

``queue: tcp://...`` points every host's worker at one TcpQueueServer
broker -- the cross-host data plane (the reference's Redis role): run N
workers on N hosts against the same broker address. ``queue:
redis://...`` is the FLEET data plane (ISSUE-9): the worker becomes
one consumer-group member on a stream broker (redis_adapter stream
mode) -- claims are acked on reply and a dead member's claims are
reclaimed by survivors (serving/fleet.py drives N such deployments).

With ``http.enabled`` the frontend OWNS the result stream (its router
consumes every worker result, HttpFrontend's contract) -- direct queue
clients should deploy with ``http.enabled: false`` and read
``app.output_queue`` themselves.
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import threading
import time
from typing import Any, Dict, Optional

from analytics_zoo_tpu.common.config import get_config
from analytics_zoo_tpu.common.log import get_logger
from analytics_zoo_tpu.inference.inference_model import InferenceModel
from analytics_zoo_tpu.obs.events import emit as emit_event
from analytics_zoo_tpu.obs.metrics import get_registry
from analytics_zoo_tpu.serving.http_frontend import HttpFrontend
from analytics_zoo_tpu.serving.queues import InputQueue, OutputQueue
from analytics_zoo_tpu.serving.worker import ServingWorker

logger = get_logger(__name__)

_M_DRAIN = get_registry().histogram(
    "zoo_serving_drain_duration_seconds",
    "Graceful-drain wait: from drain_begin until the engine finished "
    "its in-flight work (or the deadline expired)")


class ServingApp:
    """A running serving deployment: model + worker + optional HTTP.

    With a ``generation:`` config block the deployment also (or, when
    ``model:`` is omitted, *only*) hosts a
    :class:`~analytics_zoo_tpu.serving.generation.worker.GenerationWorker`
    -- same supervisor, drain, chaos and fleet seams as the predict
    worker, one frontend serving both ``/predict`` and ``/generate``.
    """

    def __init__(self, model: Optional[InferenceModel],
                 worker: Optional[ServingWorker],
                 input_queue: InputQueue, output_queue: OutputQueue,
                 frontend: Optional[HttpFrontend],
                 redis_frontend=None, reporter=None, supervisor=None,
                 gen_worker=None, gen_supervisor=None,
                 gen_input_queue=None):
        self.model = model
        self.worker = worker
        self.input_queue = input_queue
        self.output_queue = output_queue
        self.frontend = frontend
        self.redis_frontend = redis_frontend
        self.reporter = reporter
        self.supervisor = supervisor
        self.gen_worker = gen_worker
        self.gen_supervisor = gen_supervisor
        self.gen_input_queue = gen_input_queue

    @property
    def address(self) -> Optional[str]:
        return self.frontend.address if self.frontend else None

    def drain(self, deadline_ms: Optional[float] = None) -> bool:
        """Graceful drain (ISSUE-9): refuse new work, finish what is
        already in flight, within ``zoo.serving.drain.deadline_ms``.
        The SIGTERM handler and each rolling-restart step run this
        before ``stop()``; returns True when the engine fully drained
        inside the budget. Safe to call once per app."""
        if deadline_ms is None:
            deadline_ms = float(get_config().get(
                "zoo.serving.drain.deadline_ms", 10000.0))
        emit_event("drain_begin", "serving", deadline_ms=deadline_ms)
        t0 = time.monotonic()
        # supervisors first: a draining worker's thread exits with its
        # stop event unset, which must not read as a crash to restart
        if self.supervisor is not None:
            self.supervisor.stop()
        if self.gen_supervisor is not None:
            self.gen_supervisor.stop()
        if self.frontend is not None:
            # health goes 503 "draining" -> the fleet router (and any
            # LB honoring /healthz) stops sending traffic here; new
            # direct /predicts get a structured 503 + Retry-After
            self.frontend.set_draining()
        ok = True
        if self.worker is not None:
            ok = self.worker.drain(deadline_s=deadline_ms / 1000.0)
        if self.gen_worker is not None:
            # in-flight token STREAMS finish too: the generation drain
            # admits nothing new and steps until every live slot
            # reached its terminal chunk (each plane gets the full
            # budget -- they drain concurrently-started work, not a
            # shared quantity)
            ok = self.gen_worker.drain(
                deadline_s=deadline_ms / 1000.0) and ok
        waited = time.monotonic() - t0
        _M_DRAIN.observe(waited)
        emit_event("drain_complete", "serving", ok=ok,
                   waited_s=round(waited, 3))
        if not ok:
            logger.warning(
                "drain deadline (%.0f ms) expired with in-flight work "
                "remaining; stop() will cut it loose", deadline_ms)
        return ok

    def stop(self) -> None:
        # supervisors FIRST: they exist to restart a stopping worker,
        # which is exactly what an orderly shutdown must not fight
        if self.supervisor is not None:
            self.supervisor.stop()
        if self.gen_supervisor is not None:
            self.gen_supervisor.stop()
        if self.frontend is not None:
            self.frontend.stop()
        if self.redis_frontend is not None:
            self.redis_frontend.stop()
        if self.worker is not None:
            self.worker.stop()
        if self.gen_worker is not None:
            self.gen_worker.stop()
        if self.reporter is not None:
            self.reporter.stop()
        emit_event("serving_stop", "serving")
        logger.info("serving stopped")


def _load_model(cfg: Dict[str, Any]) -> InferenceModel:
    mcfg = cfg.get("model") or {}
    path = mcfg.get("path")
    if not path:
        raise ValueError("config needs model.path")
    model = InferenceModel()
    if mcfg.get("encrypted"):
        secret = mcfg.get("secret")
        if not secret:
            raise ValueError("model.encrypted needs model.secret")
        model.load_encrypted_zoo(path, secret)
    else:
        model.load_zoo(path)
    return model


def launch(config: Dict[str, Any], model: Any = None) -> ServingApp:
    """Assemble and start a deployment from a parsed config dict.

    ``model`` injects a pre-built model object instead of loading one
    from ``model.path`` -- the population path (ISSUE-13): a
    :class:`~analytics_zoo_tpu.inference.population.
    PopulationInferenceModel` is built in-process from a trained
    ``PopulationEstimator`` (``from_estimator``), not from a saved
    directory, and rides the same worker / drain / supervisor /
    frontend assembly as a loaded ``InferenceModel``. Any object
    honoring the ``predict_async(x) -> (outputs, n)`` contract works.
    """
    # fail fast on a bad conf file / AZT_* env var: every spec'd
    # zoo.* key's resolved value is checked against the type/range
    # metadata (common.config._SPECS) before any thread starts
    from analytics_zoo_tpu.common.config import validate_config

    validate_config()
    # black box first: a deployment that dies during model load /
    # warm-up should already leave a postmortem bundle. Library-level
    # install (no signal hook -- launch() may run off the main thread);
    # main() adds the SIGTERM bundle.
    if get_config().get("zoo.obs.flight.enabled", True):
        from analytics_zoo_tpu.obs.flight import install_flight_recorder

        install_flight_recorder()
    # chaos drills arm BEFORE the worker exists so launch-time seams
    # are covered too (no-op unless zoo.serving.chaos.enabled)
    from analytics_zoo_tpu.serving.chaos import maybe_install_from_config

    maybe_install_from_config()
    # generation block (ISSUE-10): presence enables the token-
    # streaming data plane (unless `enabled: false`); a deployment may
    # host generation ONLY, in which case model.path is not required
    gen_cfg = dict(config.get("generation") or {})
    # PRESENCE of the block enables the plane (a bare `generation:`
    # with every sub-key defaulted is valid), `enabled: false` opts out
    gen_enabled = ("generation" in config
                   and bool(gen_cfg.get("enabled", True)))
    if model is None:
        model = (None if gen_enabled and not config.get("model")
                 else _load_model(config))
    data = config.get("data") or {}
    params = config.get("params") or {}
    http = config.get("http") or {}

    # mesh routing (inference/sharded.py): an optional `shard:` YAML
    # block rides into plan resolution as PER-LAUNCH overrides of the
    # zoo.serving.shard.* keys -- never written into the process-global
    # config, so a later launch() in this process cannot inherit this
    # deployment's sharding. The resolved plan attaches BEFORE warm-up
    # so the bucket ladder compiles under the active mesh.
    shard_cfg = config.get("shard") or {}
    _shard_yaml_keys = {
        "mode": "zoo.serving.shard.mode",
        "recipe": "zoo.serving.shard.recipe",
        "quantized_collectives":
            "zoo.serving.shard.quantized_collectives",
        "devices": "zoo.serving.shard.devices",
    }
    from analytics_zoo_tpu.common.config import validate_config_value

    # set() is deliberately permissive and validate_config() already
    # ran above -- values arriving through the shard block must pass
    # the same launch-time spec check or the fail-fast guarantee has a
    # YAML-shaped hole
    shard_overrides = {
        cfg_key: validate_config_value(cfg_key, shard_cfg[yaml_key])
        for yaml_key, cfg_key in _shard_yaml_keys.items()
        if yaml_key in shard_cfg}
    from analytics_zoo_tpu.inference.sharded import (
        maybe_shard_from_config)

    shard_plan = (maybe_shard_from_config(model,
                                          overrides=shard_overrides)
                  if model is not None else None)

    if data.get("queue") == "dir" and not data.get("path"):
        raise ValueError('data.queue "dir" needs data.path')
    queue_kind = data.get("queue")
    # set only by the redis branch: the frontend drains its own reply
    # stream instead of the worker's default output queue
    frontend_out_q: Optional[OutputQueue] = None
    if queue_kind == "tcp":  # docstring form: queue: tcp + path: host:port
        if not data.get("path"):
            raise ValueError('data.queue "tcp" needs data.path '
                             '"host:port"')
        queue_kind = "tcp://" + str(data["path"])
    if queue_kind == "redis":  # same form: queue: redis + path: host:port
        if not data.get("path"):
            raise ValueError('data.queue "redis" needs data.path '
                             '"host:port"')
        queue_kind = "redis://" + str(data["path"])
    if isinstance(queue_kind, str) and queue_kind.startswith("redis://"):
        # fleet data plane (ISSUE-9): this deployment is ONE consumer-
        # group member on a shared stream broker (redis_adapter in
        # stream mode) -- N replicas with the same data.group shard
        # the stream; per-replica data.consumer names the PEL owner so
        # a dead replica's claims are reclaimable
        group = str(data.get("group", "serving"))
        consumer = str(data.get("consumer") or f"replica-{os.getpid()}")
        # remote replicas (ISSUE-20): the broker may live on another
        # host and may still be binding when the controller spawns us.
        # Probe it with capped backoff BEFORE building queues -- a
        # replica that cannot reach its data plane should die loudly
        # (controller sees the exit, backs off) rather than wedge in
        # a connect loop that looks like a slow start.
        from analytics_zoo_tpu.serving.redis_adapter import wait_broker

        if not wait_broker(queue_kind[len("redis://"):]):
            raise RuntimeError(
                f"fleet broker unreachable at {queue_kind} (see "
                "broker_unreachable event); refusing to start")
        in_q = InputQueue(backend=queue_kind,
                          name=str(data.get("stream", "serving_stream")),
                          group=group, consumer=consumer)
        # the worker's DEFAULT output is the broker's shared result
        # stream (the controller's drain consumes it into the
        # KEYS/HGETALL result table) -- direct stream clients get
        # their answers there no matter which replica served them
        out_q = OutputQueue(
            backend=queue_kind,
            name=str(data.get("result_stream", "result_stream")))
        if http.get("enabled", True):
            # this replica's frontend owns its own reply stream on
            # the broker (its requests carry it as reply-to, the
            # worker's _reply_backend routes results there) -- unlike
            # the tcp branch, the frontend drains ONLY that stream,
            # so direct stream traffic and HTTP traffic coexist on
            # one fleet. The name derives from the STABLE consumer
            # name, not a fresh uuid: a restarted replica re-attaches
            # to the same stream and drains what its predecessor left
            # behind -- a crash-looping replica must not mint an
            # orphaned stream (never consumed, never trimmed) per
            # cycle. Results for requests the dead frontend owned are
            # drained-and-dropped as abandoned, which is their fate
            # either way.
            reply = f"reply_{consumer}"
            in_q.reply_stream = reply
            frontend_out_q = OutputQueue(
                backend=queue_kind, name=reply,
                group=f"{reply}_g", consumer=consumer)
    elif isinstance(queue_kind, str) and queue_kind.startswith("tcp://"):
        in_q = InputQueue(backend=queue_kind)
        if http.get("enabled", True):
            # each deployment's frontend owns a UNIQUE result stream on
            # the broker (requests carry it as reply-to): N frontends
            # sharing one broker would otherwise race on one result
            # stream and drop each other's results
            import uuid as _uuid

            reply = f"result_{_uuid.uuid4().hex[:12]}"
            in_q.reply_stream = reply
            out_q = OutputQueue(backend=queue_kind, name=reply)
        else:
            out_q = OutputQueue(backend=queue_kind)
    else:
        # backend=None lets the queues module infer dir-backing from path
        in_q = InputQueue(backend=queue_kind,
                          path=data.get("path"),
                          maxlen=data.get("maxlen", 10000))
        out_q = OutputQueue(backend=queue_kind,
                            path=(data.get("path") + ".out"
                                  if data.get("path") else None))
    supervise = bool(
        get_config().get("zoo.serving.supervisor.enabled", True))
    worker = None
    supervisor = None
    if model is not None:
        worker = ServingWorker(
            model, in_q, out_q, batch_size=params.get("batch_size"),
            timeout_ms=params.get("timeout_ms"),
            top_n=params.get("top_n"),
            pipeline_depth=params.get("pipeline_depth"),
            min_timeout_ms=params.get("min_timeout_ms"),
            max_batch_size=params.get("max_batch_size"))
        from analytics_zoo_tpu.inference.inference_model import (
            bucket_ladder)

        # default: every power-of-two bucket the batcher can emit --
        # up to its backlog GROWTH cap, not just the base size -- so
        # no request ever pays a live XLA compile, least of all at the
        # first backlog spike (exactly when a multi-second compile
        # stall hurts most). Cap growth-warming with
        # params.max_batch_size for deployments that cannot afford the
        # extra startup compiles.
        warm_cap = getattr(worker.batcher, "max_batch_size",
                           worker.batcher.batch_size)
        warm = params.get("warm_batch_sizes", bucket_ladder(warm_cap))
        if warm:
            warm_example = params.get(
                "warm_example", getattr(model, "example_input", None))
            if warm_example is not None:
                model.warm_up(warm_example, batch_sizes=tuple(warm))
            else:
                logger.warning(
                    "warm_batch_sizes set but no example input is "
                    "available; skipping warm-up")
        worker.start()
        if supervise:
            # the recovery story (ISSUE-5): restart a dead/wedged
            # worker with backoff, re-queue its in-flight requests
            # exactly once
            from analytics_zoo_tpu.serving.resilience import Supervisor

            supervisor = Supervisor(worker).start()
    gen_worker = None
    gen_supervisor = None
    gen_in = None
    frontend = None
    redis_fe = None
    reporter = None
    try:
        if gen_enabled:
            # generation data plane (ISSUE-10): its OWN request
            # stream (brokered backends shard it across fleet
            # replicas through the same consumer group as the predict
            # stream), the shared default result stream, and the same
            # supervisor/drain machinery as the predict worker
            from analytics_zoo_tpu.serving.generation.engine import (
                engine_from_config)
            from analytics_zoo_tpu.serving.generation.worker import (
                GenerationWorker)

            gen_stream = str(gen_cfg.get("stream", "generation_stream"))
            # disaggregated pools (ISSUE-20): a prefill replica admits
            # + prefills and hands each stream to the decode pool over
            # the broker's handoff stream; a decode replica consumes
            # ONLY that stream. The handoff stream is consumer-grouped
            # like the request stream, so a SIGKILLed decode replica's
            # unfinished handoffs are reclaimed by survivors.
            gen_role = str(gen_cfg.get("role", "unified"))
            handoff_stream = str(gen_cfg.get(
                "handoff_stream", "generation_handoff_stream"))
            handoff_out = None
            if gen_role != "unified" and not (
                    isinstance(queue_kind, str)
                    and queue_kind.startswith("redis://")):
                raise ValueError(
                    f"generation.role {gen_role!r} needs data.queue "
                    "redis:// -- the prefill->decode handoff stream "
                    "lives on the fleet broker")
            if isinstance(queue_kind, str) and (
                    queue_kind.startswith("tcp://")
                    or queue_kind.startswith("redis://")):
                if queue_kind.startswith("redis://"):
                    gen_group = str(data.get("group", "serving"))
                    gen_consumer = str(data.get("consumer")
                                       or f"replica-{os.getpid()}")
                    if gen_role == "decode":
                        # the decode pool shards the HANDOFF stream
                        # under its own group (prefill replicas share
                        # the request-stream group); a dead member's
                        # pending handoffs ride the PEL to a survivor
                        gen_in = InputQueue(
                            backend=queue_kind, name=handoff_stream,
                            group=f"{gen_group}_decode",
                            consumer=gen_consumer)
                    else:
                        gen_in = InputQueue(
                            backend=queue_kind, name=gen_stream,
                            group=gen_group, consumer=gen_consumer)
                    if gen_role in ("prefill", "decode"):
                        # prefill PUBLISHES handoffs; decode publishes
                        # too, at drain time, to move its live streams
                        # to a pool survivor before exiting
                        handoff_out = OutputQueue(
                            backend=queue_kind, name=handoff_stream)
                else:
                    gen_in = InputQueue(backend=queue_kind,
                                        name=gen_stream)
                # chunks route back to THIS frontend's reply stream,
                # exactly like predict results
                gen_in.reply_stream = in_q.reply_stream
            elif data.get("queue") == "dir" and data.get("path"):
                # cross-process spool deployments keep their contract:
                # a sibling spool directory, so external producers can
                # enqueue generate requests the same way they enqueue
                # predicts (a silent in-memory fallback would strand
                # them with no consumer)
                gen_in = InputQueue(backend="dir",
                                    path=str(data["path"]) + ".gen",
                                    maxlen=data.get("maxlen", 10000))
            else:
                gen_in = InputQueue(backend="memory",
                                    maxlen=data.get("maxlen", 10000))
            engine = engine_from_config(gen_cfg)
            # the generate path's warm-up contract: compile the whole
            # prefill ladder + the decode step before traffic, so a
            # launch mints zero storm-eligible compiles
            engine.warm_up()
            gen_worker = GenerationWorker(
                engine, gen_in, out_q,
                max_tokens=gen_cfg.get("max_tokens"),
                eos=gen_cfg.get("eos"),
                stream_chunk_tokens=gen_cfg.get(
                    "stream_chunk_tokens"),
                role=gen_role, handoff_queue=handoff_out).start()
            if supervise:
                from analytics_zoo_tpu.serving.resilience import (
                    Supervisor)

                gen_supervisor = Supervisor(gen_worker).start()
        if http.get("enabled", True):
            port = http.get("port")
            if port is None:
                # zoo.serving.http_port (0 = pick a free port); the
                # YAML's http.port wins when present
                port = int(get_config().get("zoo.serving.http_port", 0))
            frontend = HttpFrontend(
                in_q,
                out_q if frontend_out_q is None else frontend_out_q,
                # no YAML host -> zoo.serving.fleet.bind_host (the
                # frontend's config-driven default; loopback unless
                # the deployment opts into a routable bind)
                host=http.get("host"),
                port=port, worker=worker,
                certfile=http.get("certfile"),
                keyfile=http.get("keyfile"),
                gen_queue=gen_in, gen_worker=gen_worker).start()
            logger.info("serving ready at %s", frontend.address)
        redis_cfg = config.get("redis") or {}
        if redis_cfg.get("enabled"):
            # reference-client interop: a RESP2 adapter speaking the
            # cluster-serving Redis-stream + Arrow wire format
            # (redis_adapter.py). The adapter DRAINS the output queue,
            # exactly like the HTTP frontend's result router -- two
            # drainers on one queue would steal each other's results
            # nondeterministically, so the combination is rejected
            # here rather than discovered as hung clients
            if frontend is not None:
                raise ValueError(
                    "redis.enabled requires http.enabled: false -- "
                    "both frontends drain the same result queue (use "
                    "two deployments on a shared tcp broker to serve "
                    "both protocols)")
            from analytics_zoo_tpu.serving.redis_adapter import (
                RedisFrontend)

            redis_fe = RedisFrontend(
                in_q, out_q, host=redis_cfg.get("host"),
                port=int(redis_cfg.get("port", 6379)),
                name=redis_cfg.get("stream", "serving_stream")).serve()
        # config-gated rollup logger (zoo.obs.report.interval seconds;
        # 0 = off): the deployment's periodic rate/latency log line.
        # Inside the guard: a malformed interval value must not leak
        # the already-running worker/frontends
        from analytics_zoo_tpu.obs.reporter import maybe_start_reporter

        reporter = maybe_start_reporter()
    except Exception as e:
        emit_event("launch_failed", "serving", error=repr(e)[:500])
        # no ServingApp handle escapes; don't leak running pieces
        # (supervisors first, or they would restart the workers we
        # stop)
        if supervisor is not None:
            supervisor.stop()
        if gen_supervisor is not None:
            gen_supervisor.stop()
        if frontend is not None:
            frontend.stop()
        if redis_fe is not None:
            redis_fe.stop()
        if worker is not None:
            worker.stop()
        if gen_worker is not None:
            gen_worker.stop()
        raise
    emit_event(
        "serving_launch", "serving",
        queue=str(data.get("queue") or "memory"),
        http=bool(http.get("enabled", True)),
        shard_mode=(shard_plan.label if shard_plan is not None
                    else "off"),
        generation=gen_worker is not None,
        address=frontend.address if frontend is not None else None)
    return ServingApp(model, worker, in_q, out_q, frontend,
                      redis_frontend=redis_fe, reporter=reporter,
                      supervisor=supervisor, gen_worker=gen_worker,
                      gen_supervisor=gen_supervisor,
                      gen_input_queue=gen_in)


def launch_from_yaml(path: str) -> ServingApp:
    import yaml

    with open(path) as f:
        return launch(yaml.safe_load(f) or {})


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(
        description="analytics_zoo_tpu serving launcher")
    ap.add_argument("-c", "--config", required=True,
                    help="path to the serving YAML config")
    ap.add_argument("--ready-file",
                    help="write {pid, address, started_at} JSON here "
                         "once the deployment is serving (the fleet "
                         "controller's readiness/address channel)")
    args = ap.parse_args(argv)
    app = launch_from_yaml(args.config)
    if args.ready_file:
        address = app.address
        # cross-host fleets (ISSUE-20): the frontend binds
        # zoo.serving.fleet.bind_host (often 0.0.0.0 in a container),
        # but the CONTROLLER must route to an address reachable from
        # its host -- zoo.serving.fleet.advertise_host, when set,
        # replaces the bound host in the readiness address
        adv = str(get_config().get(
            "zoo.serving.fleet.advertise_host", "") or "")
        if adv and address and ":" in address:
            address = f"{adv}:{address.rsplit(':', 1)[1]}"
        tmp = args.ready_file + ".tmp"
        with open(tmp, "w") as f:
            json.dump({"pid": os.getpid(), "address": address,
                       "started_at": time.time()}, f)
        os.replace(tmp, args.ready_file)  # atomic: never half-read
    stop = threading.Event()

    def handler(signum, frame):
        stop.set()

    signal.signal(signal.SIGINT, handler)
    signal.signal(signal.SIGTERM, handler)
    if get_config().get("zoo.obs.flight.enabled", True):
        # SIGTERM bundle: the recorder's hook writes the postmortem,
        # then chains to `handler` above (installed first) for the
        # graceful drain -- orchestrated kills leave an artifact AND
        # shut down cleanly
        from analytics_zoo_tpu.obs.flight import install_flight_recorder

        install_flight_recorder(signals=True)
    stop.wait()
    # SIGTERM used to stop immediately, abandoning in-flight requests
    # (ISSUE-9 satellite): drain first -- stop pulling, answer what
    # was already accepted -- under zoo.serving.drain.deadline_ms
    # (0 restores the old cut-now behavior); rolling restarts lean on
    # this exact seam
    if float(get_config().get("zoo.serving.drain.deadline_ms",
                              10000.0)) > 0:
        app.drain()
    app.stop()


if __name__ == "__main__":
    main()
