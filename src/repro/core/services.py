"""Built-in microservices (the PhenoMeNal-style 'community of practice'
package set): data pipeline, LM trainer, serving engines + edge router,
workflow system, volumes (checkpoint store), monitoring dashboard.

Each builder returns a ``ServiceHandle`` — the uniform lifecycle protocol
(``start/stop/health/scale/metrics``) the VRE orchestrator manages — wrapping
the live instance; builders use the VRE's image cache for their expensive
artifacts where possible.
"""
from __future__ import annotations

import jax
import numpy as np

from repro.checkpoint.store import CheckpointStore
from repro.configs import served_config
from repro.core.registry import ServiceHandle, register_service
from repro.core.scheduler import ClusterScheduler
from repro.core.vre import PROVIDER_PLATFORMS
from repro.core.workflow import Workflow
from repro.data.pipeline import DataConfig, SyntheticLMData
from repro.models.model import build_model, init_params
from repro.optim.adamw import OptimizerConfig
from repro.serving.autoscaler import Autoscaler, AutoscalerConfig
from repro.serving.engine import EdgeRouter, ServingEngine
from repro.serving.prefix_cache import PrefixCache
from repro.serving.replica import ReplicaSet
from repro.serving.speculative import build_draft, supports_speculation
from repro.training.train_step import (TrainStepConfig, init_state,
                                       make_train_step)


def _model_cfg(ctx):
    return served_config(ctx.config.arch or "yi-9b",
                         PROVIDER_PLATFORMS[ctx.config.provider])


_SERVED_MODEL_CACHE: dict = {}
_SERVED_MODEL_LOCK = __import__("threading").Lock()


def _served_model(ctx):
    """(cfg, model, params) for the serving plane, cached across VREs and
    re-instantiations — the compiled-kernel analogue of the deployment
    image cache. An elastic resize (or a fleet preemption) rebuilds the
    service; a fresh model object would drop the engine jit cache shared
    through it and pay a full prefill/decode recompile at the worst
    possible moment (right after the resize, under the very load that
    triggered it). Keyed by what ``_model_cfg`` derives the config from;
    params are deterministic (fixed seed), so sharing them across VREs of
    the same arch is observationally identical to rebuilding.

    The params are built on the mesh's first device, the one the first
    replica serves from: its engine then aliases them instead of holding a
    second copy."""
    key = (ctx.config.arch or "yi-9b", ctx.config.provider)
    with _SERVED_MODEL_LOCK:
        ent = _SERVED_MODEL_CACHE.get(key)
        if ent is None:
            cfg = _model_cfg(ctx)
            model = build_model(cfg)
            device = ctx.mesh.devices.flat[0] if ctx.mesh is not None \
                else None
            params = init_params(model, jax.random.PRNGKey(0), device)
            ent = (cfg, model, params)
            _SERVED_MODEL_CACHE[key] = ent
    return ent


@register_service("volumes", "storage",
                  description="GlusterFS analogue: sharded checkpoint store")
def build_volumes(ctx):
    store = CheckpointStore(str(ctx.workdir / ctx.config.name / "volumes"),
                            num_servers=ctx.config.storage_servers)
    return ServiceHandle("volumes", "storage", store)


@register_service("data", "data",
                  description="host-sharded synthetic token pipeline")
def build_data(ctx):
    cfg = _model_cfg(ctx)
    batch = int(ctx.config.extra.get("global_batch", 8))
    seq = int(ctx.config.extra.get("seq_len", 64))
    data = SyntheticLMData(DataConfig(
        vocab_size=cfg.vocab_size, seq_len=seq, global_batch=batch,
        embeddings_dim=cfg.d_model if cfg.input_mode == "embeddings" else 0))
    return ServiceHandle("data", "data", data)


class TrainerService(ServiceHandle):
    """LM training service: jitted train_step over mutable optimizer state."""

    def __init__(self, ctx, cfg, model, state, axes, jit_step):
        super().__init__("lm-trainer", "train", model)
        self.ctx = ctx
        self.cfg = cfg
        self.model = model
        self.state = state
        self.axes = axes
        self.step = 0
        self.history = []
        self._jit_step = jit_step

    def train_steps(self, data, n: int):
        it = iter(data)
        for _ in range(n):
            batch = jax.tree.map(jax.numpy.asarray, next(it))
            self.state, metrics = self._jit_step(self.state, batch)
            self.step += 1
            loss = float(metrics["loss"])
            self.history.append(loss)
            self.ctx.monitor.log("lm-trainer", "step", step=self.step,
                                 loss=loss)
        return self.history[-n:]

    def health(self) -> bool:
        return not self.history or bool(np.isfinite(self.history[-1]))

    def metrics(self) -> dict:
        return {"step": self.step,
                "loss": self.history[-1] if self.history else None}


@register_service("lm-trainer", "train",
                  description="LM training service (train_step + state)")
def build_trainer(ctx):
    cfg = _model_cfg(ctx)
    model = build_model(cfg)
    opt_cfg = OptimizerConfig(warmup_steps=2, total_steps=100)
    mb = int(ctx.config.extra.get("microbatches", 1))
    step_fn = make_train_step(model, cfg, opt_cfg,
                              TrainStepConfig(microbatches=mb))
    state, axes = init_state(model, opt_cfg, jax.random.PRNGKey(0))
    jit_step = jax.jit(step_fn, donate_argnums=(0,))
    return TrainerService(ctx, cfg, model, state, axes, jit_step)


class ServingService(ServiceHandle):
    """Serving plane: ReplicaSet of async engines behind an edge router,
    with an optional load-driven autoscaler."""

    def __init__(self, replicaset: ReplicaSet, router: EdgeRouter,
                 autoscaler: Autoscaler = None):
        super().__init__("lm-server", "serve", replicaset)
        self.replicaset = replicaset
        self.router = router
        self.autoscaler = autoscaler

    def start(self):
        self.replicaset.start()
        if self.autoscaler is not None:
            self.autoscaler.run()
        return self

    def stop(self):
        if self.autoscaler is not None:
            self.autoscaler.stop()
        self.replicaset.stop()

    def health(self) -> bool:
        return bool(self.replicaset.healthy_engines())

    def scale(self, n: int) -> int:
        return self.replicaset.scale_to(n)

    def rebalance(self, mesh) -> dict:
        return self.replicaset.rebalance(mesh)

    def metrics(self) -> dict:
        return self.replicaset.metrics()

    def drain(self, timeout: float = 120.0):
        self.router.drain(timeout)


@register_service("lm-server", "serve",
                  description="async serving replicas + edge router + "
                              "autoscaler")
def build_server(ctx):
    cfg, model, params = _served_model(ctx)
    replicas_cfg = ctx.config.extra.get("replicas", 2)
    if replicas_cfg == "auto":
        # one replica per granted mesh device: a fleet-arbitrated grant
        # change then genuinely changes serving capacity on re-instantiation
        replicas = max(1, int(ctx.mesh.devices.size)
                       if ctx.mesh is not None else 1)
    else:
        replicas = int(replicas_cfg)
    slots = int(ctx.config.extra.get("slots", 2))
    max_seq = int(ctx.config.extra.get("max_seq", 128))
    chunk_tokens = int(ctx.config.extra.get("chunk_tokens", 0))
    prefix_cache_mb = float(ctx.config.extra.get("prefix_cache_mb", 0))
    prefix_cache = None
    shared = ctx.config.extra.get("shared_prefix_cache")
    if shared is not None and chunk_tokens \
            and getattr(shared, "chunk", None) == chunk_tokens:
        # fleet-shared cache (FleetArbiter): VREs serving the same arch
        # warm each other's prompt heads; entries are host-side, so the
        # cache outlives any one VRE's placement
        prefix_cache = shared
    elif chunk_tokens and prefix_cache_mb > 0:
        prefix_cache = PrefixCache(chunk_tokens,
                                   budget_bytes=int(prefix_cache_mb * 2**20),
                                   monitor=ctx.monitor)

    slots_per_device = ctx.config.extra.get("slots_per_device")
    speculate = int(ctx.config.extra.get("speculate", 0) or 0)
    draft_kind = str(ctx.config.extra.get("draft", "ngram"))

    recorder = None
    record_path = ctx.config.extra.get("record_path")
    if record_path:
        from repro.observability import Recorder
        # append mode: every re-instantiation (elastic resize, fleet
        # preemption) re-stamps a meta header and keeps writing to the same
        # file, so one store holds the request's whole multi-generation story
        generation = int(getattr(ctx.vre, "generation", 0) or 0)
        context = {"generation": generation}
        arbiter = getattr(ctx.vre, "arbiter", None)
        wait = getattr(arbiter, "_queue_wait_s", {}).get(ctx.config.name) \
            if arbiter is not None else None
        if wait is not None:
            context["admission_wait_s"] = round(float(wait), 6)
        recorder = Recorder(
            record_path, tenant=ctx.config.name, monitor=ctx.monitor,
            meta={"arch": ctx.config.arch or "yi-9b",
                  "provider": ctx.config.provider,
                  "generation": generation,
                  "mesh_shape": list(ctx.config.mesh_shape),
                  "serving": {"replicas": replicas_cfg, "slots": slots,
                              "max_seq": max_seq,
                              "chunk_tokens": chunk_tokens,
                              "prefix_cache_mb": prefix_cache_mb,
                              "speculate": speculate,
                              "draft": draft_kind}},
            context=context)
    # don't build drafts the engine would gate off anyway (rolling/SSM/MoE):
    # the engine still logs speculative_unsupported via its own check
    spec_supported = bool(speculate) and supports_speculation(model, max_seq)

    def factory(i: int, devices=None) -> ServingEngine:
        eng_slots, eng_devices = slots, devices
        if slots_per_device and devices:
            # granted devices buy KV-cache capacity: decode slots scale
            # with the replica's slice (aggregate HBM holds that many
            # concurrent sequences). Compute commits to the slice's lead
            # device — intra-replica sharding is a separate road-map item,
            # and *replicating* compute across the slice would burn the
            # very capacity the grant added.
            eng_slots = int(slots_per_device) * len(devices)
            eng_devices = tuple(devices[:1])
        draft = None
        if spec_supported:
            # one draft per replica: its KV state lives on the replica's
            # device slice and is rebuilt by this factory on failover/
            # respawn/rebalance — same lifecycle as the replica itself,
            # while the draft *model and params* (and through them the jit
            # cache) are shared fleet-wide like the target's
            draft = build_draft(draft_kind, cfg, slots=eng_slots,
                                max_seq=max_seq, devices=eng_devices,
                                name=f"replica{i}-draft")
        return ServingEngine(model, params, slots=eng_slots,
                             max_seq=max_seq, name=f"replica{i}",
                             monitor=ctx.monitor, devices=eng_devices,
                             chunk_tokens=chunk_tokens,
                             prefix_cache=prefix_cache,
                             speculate=speculate, draft=draft,
                             recorder=recorder)

    # the ReplicaSet partitions the VRE mesh into disjoint per-replica
    # slices, so "scale the mesh" genuinely changes the hardware replicas
    # occupy (not just thread counts)
    rs = ReplicaSet(factory, replicas=replicas, monitor=ctx.monitor,
                    mesh=ctx.mesh, prefix_cache=prefix_cache,
                    recorder=recorder)
    router = EdgeRouter(rs)
    autoscaler = None
    if ctx.config.extra.get("autoscale"):
        as_cfg = AutoscalerConfig(
            min_replicas=int(ctx.config.extra.get("min_replicas", 1)),
            max_replicas=int(ctx.config.extra.get("max_replicas",
                                                  max(replicas, 4))),
            scale_up_prefill_tokens=(
                float(ctx.config.extra["scale_up_prefill_tokens"])
                if ctx.config.extra.get("scale_up_prefill_tokens") is not None
                else None))
        slo_engine = None
        slo_cfg = ctx.config.extra.get("slo")
        if isinstance(slo_cfg, dict) and slo_cfg:
            # declarative SLO targets ride the autoscaler: error-budget burn
            # becomes a growth trigger alongside raw load, and the burn rate
            # travels with resize proposals into the arbiter
            from repro.observability.slo import SLOEngine, targets_from_config
            slo_engine = SLOEngine(
                ctx.monitor, targets_from_config(slo_cfg),
                services=lambda: [e.name for e in rs.engines],
                burn_threshold=float(slo_cfg.get("burn_threshold", 1.0)),
                name=f"{ctx.config.name}-slo")
        autoscaler = Autoscaler(rs, ctx.monitor, as_cfg,
                                resize_mesh=getattr(ctx.vre, "request_resize",
                                                    None),
                                slo=slo_engine)
    return ServingService(rs, router, autoscaler)


class WorkflowService(ServiceHandle):
    def __init__(self, scheduler: ClusterScheduler):
        super().__init__("workflows", "workflow", scheduler)
        self.scheduler = scheduler

    def new(self, name: str) -> Workflow:
        return Workflow(name)

    def run(self, wf: Workflow):
        return self.scheduler.run(wf)

    def scale(self, n: int) -> int:
        return getattr(self.scheduler, "num_workers", 1)


@register_service("workflows", "workflow",
                  description="Luigi/Pachyderm analogue: DAG tool scheduler")
def build_workflows(ctx):
    sched = ClusterScheduler(
        num_workers=int(ctx.config.extra.get("workers", 4)),
        monitor=ctx.monitor)
    return WorkflowService(sched)


class DashboardService(ServiceHandle):
    def __init__(self, monitor):
        super().__init__("dashboard", "monitor", monitor)
        self.summary = monitor.summarize
        self.events = monitor.events
        self.gauges = monitor.gauges

    def metrics(self) -> dict:
        return self.instance.summarize()


@register_service("dashboard", "monitor",
                  description="EFK analogue: metrics aggregation")
def build_dashboard(ctx):
    return DashboardService(ctx.monitor)
