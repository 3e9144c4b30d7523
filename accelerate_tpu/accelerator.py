"""The Accelerator: the user-facing facade (L5).

TPU-native redesign of reference accelerator.py (3409 LoC). The ergonomic contract is
preserved — construct one object, `prepare()` your objects, train with
`accumulate()`/`backward()`/`step()`, evaluate with `gather_for_metrics()`, checkpoint
with `save_state()`/`load_state()` — while the machinery underneath is GSPMD:

  - `prepare(model)` derives NamedShardings from the active plugins and places params on
    the mesh (replaces the DDP/FSDP/DeepSpeed/Megatron branch tree,
    reference accelerator.py:1248-1295,1414-1886).
  - `backward(loss_fn, batch)` runs a jitted value_and_grad; gradient cross-replica
    reduction is *implicit* in the sharded-batch loss (no NCCL hooks, no `no_sync`
    machinery — the reference's `xm.all_reduce`-once-per-step trick at
    optimizer.py:140-146 becomes a compiler decision).
  - `accumulate()` keeps the reference's eager-feel contract (`_do_sync`,
    end-of-dataloader forcing, reference accelerator.py:999-1057) while each microbatch
    is one jitted call with donated accumulation buffers.

The canonical loop::

    accelerator = Accelerator(mixed_precision="bf16")
    model, optimizer, train_dl, scheduler = accelerator.prepare(model, optimizer, train_dl, scheduler)
    for batch in train_dl:
        with accelerator.accumulate(model):
            loss = accelerator.backward(model.loss, batch)
            optimizer.step()
            scheduler.step()
            optimizer.zero_grad()

where `model.loss(params, batch)` is any differentiable scalar function of the params.
"""

from __future__ import annotations

import contextlib
import functools
import math
import os
import re
import time
from typing import Any, Callable, List, Optional, Union

import numpy as np

from .checkpointing import (
    AdaptiveSaveInterval,
    AsyncCommitter,
    CheckpointCommitError,
    CheckpointManager,
    is_sharded_checkpoint_dir,
    load_accelerator_state,
    load_custom_state,
    load_sharded_accelerator_state,
    save_accelerator_state,
    save_custom_state,
    sharded_manifest_extra,
    snapshot_accelerator_state,
    write_accelerator_snapshot,
    write_checkpoint_manifest,
)
from .data_loader import DataLoaderDispatcher, DataLoaderShard, SimpleDataLoader, prepare_data_loader, skip_first_batches
from .logging import get_logger
from .modeling import Model, PreparedModel
from .optimizer import AcceleratedOptimizer, GradScaler
from .scheduler import AcceleratedScheduler
from .state import AcceleratorState, GradientState, PartialState
from .telemetry import MetricsRegistry, ProfilerManager, StepTimeline
from .telemetry.tracing import default_tracer
from .tracking import LOGGER_TYPE_TO_CLASS, GeneralTracker, filter_trackers
from .utils import operations as ops
from .utils.dataclasses import (
    AutocastKwargs,
    FP8RecipeKwargs,
    CompilationConfig,
    DataLoaderConfiguration,
    DeepSpeedPlugin,
    DistributedDataParallelKwargs,
    DistributedType,
    FullyShardedDataParallelPlugin,
    GradientAccumulationPlugin,
    GradScalerKwargs,
    InitProcessGroupKwargs,
    KwargsHandler,
    MegatronLMPlugin,
    ParallelismConfig,
    PrecisionType,
    ProjectConfiguration,
    SequenceParallelPlugin,
)
from .utils.environment import parse_flag_from_env
from .utils.random import set_seed

logger = get_logger(__name__)


class Accelerator:
    """Creates the distributed environment and owns object preparation
    (reference accelerator.py:163)."""

    def __init__(
        self,
        device_placement: bool = True,
        split_batches: bool = False,
        mixed_precision: Optional[str] = None,
        gradient_accumulation_steps: int = 1,
        cpu: bool = False,
        dataloader_config: Optional[DataLoaderConfiguration] = None,
        log_with=None,
        project_dir: Optional[str] = None,
        project_config: Optional[ProjectConfiguration] = None,
        gradient_accumulation_plugin: Optional[GradientAccumulationPlugin] = None,
        parallelism_config: Optional[ParallelismConfig] = None,
        fsdp_plugin: Optional[FullyShardedDataParallelPlugin] = None,
        deepspeed_plugin: Optional[DeepSpeedPlugin] = None,
        megatron_lm_plugin: Optional[MegatronLMPlugin] = None,
        sequence_parallel_plugin: Optional[SequenceParallelPlugin] = None,
        compilation_config: Optional[CompilationConfig] = None,
        rng_types: Optional[List[str]] = None,
        kwargs_handlers: Optional[List[KwargsHandler]] = None,
        step_scheduler_with_optimizer: bool = True,
        analyze: bool = False,
        tracer=None,
        async_save: Optional[bool] = None,
        sharded_save: Optional[bool] = None,
        save_interval: Optional[Union[int, str]] = None,
        lost_checkpoint_s: float = 300.0,
    ):
        self.project_configuration = project_config or ProjectConfiguration(project_dir=project_dir)
        if project_dir is not None and self.project_configuration.project_dir is None:
            self.project_configuration.set_directories(project_dir)

        # analyze=True arms the runtime half of `accelerate analyze`: every
        # train_step() built from this Accelerator is wrapped in a TraceGuard
        # that (after a warmup allowance) raises when a steady-state step
        # recompiles or makes a guarded host transfer. See docs/analysis.md.
        self.analyze = bool(analyze)
        self.trace_guard = None
        if self.analyze:
            from .analysis import TraceGuard

            self.trace_guard = TraceGuard(name="train-step", on_violation="raise")

        if mixed_precision is not None:
            mixed_precision = str(mixed_precision)
            if mixed_precision not in PrecisionType:
                raise ValueError(f"Unknown mixed_precision mode: {mixed_precision}; choose {PrecisionType.list()}")

        # kwargs handlers (reference accelerator.py:338-375)
        self.scaler_handler = None
        self.init_handler = None
        self.autocast_handler = None
        self.ddp_handler = None
        self.fp8_recipe_handler = None
        for handler in kwargs_handlers or []:
            if isinstance(handler, GradScalerKwargs):
                self.scaler_handler = handler
            elif isinstance(handler, InitProcessGroupKwargs):
                self.init_handler = handler
            elif isinstance(handler, AutocastKwargs):
                self.autocast_handler = handler
            elif isinstance(handler, DistributedDataParallelKwargs):
                self.ddp_handler = handler  # accepted for parity; no-op under GSPMD
            elif isinstance(handler, FP8RecipeKwargs):
                self.fp8_recipe_handler = handler

        init_kwargs = {}
        if self.init_handler is not None and self.init_handler.timeout is not None:
            init_kwargs["timeout"] = self.init_handler.timeout
        if fsdp_plugin is None and parse_flag_from_env("ACCELERATE_TPU_USE_FSDP"):
            fsdp_plugin = FullyShardedDataParallelPlugin()
        if sequence_parallel_plugin is None and os.environ.get("ACCELERATE_TPU_SP_MODE"):
            from .utils import SequenceParallelPlugin

            sequence_parallel_plugin = SequenceParallelPlugin(
                seq_degree=int(os.environ.get("ACCELERATE_TPU_MESH_SEQ", "1") or 1),
                mode=os.environ["ACCELERATE_TPU_SP_MODE"],
                block_size=int(os.environ.get("ACCELERATE_TPU_SP_BLOCK_SIZE", "512")),
            )

        self.state = AcceleratorState(
            mixed_precision=mixed_precision,
            cpu=cpu,
            parallelism_config=parallelism_config,
            fsdp_plugin=fsdp_plugin,
            deepspeed_plugin=deepspeed_plugin,
            megatron_lm_plugin=megatron_lm_plugin,
            sequence_parallel_plugin=sequence_parallel_plugin,
            _from_accelerator=True,
            **init_kwargs,
        )

        if gradient_accumulation_plugin is None:
            gas = int(os.environ.get("ACCELERATE_TPU_GRADIENT_ACCUMULATION_STEPS", gradient_accumulation_steps))
            gradient_accumulation_plugin = GradientAccumulationPlugin(num_steps=gas)
        self.gradient_state = GradientState(gradient_accumulation_plugin=gradient_accumulation_plugin)

        self.device_placement = device_placement
        self.split_batches = split_batches
        self.dataloader_config = dataloader_config or DataLoaderConfiguration(split_batches=split_batches)
        self.compilation_config = compilation_config or CompilationConfig()
        self.step_scheduler_with_optimizer = step_scheduler_with_optimizer
        self.rng_types = rng_types or ["python", "numpy"]

        self.scaler = None
        if self.state.mixed_precision == "fp16":
            self.scaler = GradScaler(self.scaler_handler)

        # trackers
        self.log_with = filter_trackers(log_with, self.logging_dir)
        self.trackers: List[GeneralTracker] = []

        # prepared-object registries (reference accelerator.py keeps _models/_optimizers/...)
        self._models: List[PreparedModel] = []
        self._optimizers: List[AcceleratedOptimizer] = []
        self._schedulers: List[AcceleratedScheduler] = []
        self._dataloaders: List[Any] = []
        self._custom_objects: List[Any] = []
        self._backward_cache: dict = {}
        self._save_model_hooks: List[Callable] = []
        self._load_model_hooks: List[Callable] = []
        # Global batch observed on a co-prepared dataloader (prepare() peeks
        # before placing models): sizes the MPMD microbatch schedule.
        self._planning_batch_hint: Optional[int] = None

        self.step = 0
        self.flag_tensor = None

        # Telemetry (the observability pillar, docs/observability.md): one
        # registry for this Accelerator's instruments, a StepTimeline splitting
        # per-step wall clock + keeping the goodput ledger, and a
        # ProfilerManager armed from the launch env protocol
        # (ACCELERATE_TPU_PROFILE_DIR, set by `launch --profile_dir`) for
        # touch-file / SIGUSR2 on-demand capture. All construction is host-only
        # and free when profiling wasn't requested.
        self.telemetry = MetricsRegistry()
        # Request-scoped tracing + the crash/hang flight recorder: the tracer
        # comes from the launch env protocol (ACCELERATE_TPU_TRACE_DIR/_ID/
        # _PARENT, set by `launch --trace_dir` and the Supervisor) unless the
        # caller hands one in. When a trace dir is armed, exit/SIGTERM dumps,
        # the compile-event listener, and the hang watchdog
        # (ACCELERATE_TPU_HANG_DEADLINE_S, default 300 s without a step
        # heartbeat) arm with it — the next r05-style stall dumps its own
        # timeline and thread stacks instead of dying silent.
        self.tracer = tracer if tracer is not None else default_tracer()
        self.hang_watchdog = None
        recorder = getattr(self.tracer, "recorder", None)
        if recorder is not None and getattr(recorder, "log_dir", None):
            recorder.install_exit_hooks()
            self.tracer.attach_compile_listener()
            deadline = float(os.environ.get("ACCELERATE_TPU_HANG_DEADLINE_S", "300") or 0)
            if deadline > 0:
                self.hang_watchdog = recorder.start_watchdog(
                    deadline_s=deadline, tracer=self.tracer
                )
        self.timeline = StepTimeline(self.telemetry, prefix="train", tracer=self.tracer)
        self.profiler = ProfilerManager.from_env(registry=self.telemetry)
        self._m_ckpt_saves = self.telemetry.counter(
            "checkpoint_saves_total", help="save_state() completions"
        )
        self._m_ckpt_seconds = self.telemetry.histogram(
            "checkpoint_save_seconds", help="wall-clock per save_state()"
        )
        self._m_ckpt_loads = self.telemetry.counter(
            "checkpoint_loads_total", help="load_state() completions (restart recoveries)"
        )

        # Async/sharded checkpointing (docs/guides/checkpointing.md): with
        # `async_save` the train loop only pays for the device->host snapshot
        # (and a barrier on the PREVIOUS commit when it is still in flight);
        # serialize+fsync+publish run on a background committer whose time is
        # `checkpoint_async_commit_seconds`, not goodput-lost step time. With
        # `sharded_save` each process writes only its addressable shards into a
        # per-host subdirectory. Defaults ride the launch env protocol
        # (`launch --async_save` / `--sharded_save`).
        if async_save is None:
            async_save = parse_flag_from_env("ACCELERATE_TPU_ASYNC_SAVE")
        if sharded_save is None:
            sharded_save = parse_flag_from_env("ACCELERATE_TPU_SHARDED_SAVE")
        self.async_save = bool(async_save)
        self.sharded_save = bool(sharded_save)
        self._async_committer: Optional[AsyncCommitter] = None
        # Checkpoint cadence (ROADMAP 4b): `save_interval="auto"` derives the
        # save interval from the goodput ledger's measured blocking save cost
        # against the `lost_checkpoint_s` budget (work a crash may lose); an
        # int is the classic fixed every-N-steps cadence. Either arms
        # `maybe_save_state()` as the step-boundary driver.
        self.save_controller: Optional[AdaptiveSaveInterval] = None
        if save_interval == "auto":
            self.save_controller = AdaptiveSaveInterval(lost_checkpoint_s=lost_checkpoint_s)
        elif save_interval is not None:
            self.save_controller = AdaptiveSaveInterval(
                lost_checkpoint_s=lost_checkpoint_s, fixed_interval=int(save_interval)
            )
        self._steps_since_save = 0
        self._last_step_boundary: Optional[float] = None
        self._m_ckpt_commit_seconds = self.telemetry.histogram(
            "checkpoint_async_commit_seconds",
            help="background (async) checkpoint commit wall-clock — overlapped "
            "with training, NOT charged to the goodput ledger",
        )
        self._g_ckpt_in_flight = self.telemetry.gauge(
            "checkpoint_commits_in_flight", help="async checkpoint commits currently running"
        )

        if self.compilation_config.cache_dir:
            from .utils.environment import configure_compile_cache

            configure_compile_cache(self.compilation_config.cache_dir)

    # ------------------------------------------------------------------ state passthrough
    @property
    def distributed_type(self) -> DistributedType:
        return self.state.distributed_type

    @property
    def num_processes(self) -> int:
        return self.state.num_processes

    @property
    def process_index(self) -> int:
        return self.state.process_index

    @property
    def local_process_index(self) -> int:
        return self.state.local_process_index

    @property
    def device(self):
        return self.state.device

    @property
    def mesh(self):
        return self.state.mesh

    @property
    def is_main_process(self) -> bool:
        return self.state.is_main_process

    @property
    def is_local_main_process(self) -> bool:
        return self.state.is_local_main_process

    @property
    def is_last_process(self) -> bool:
        return self.state.is_last_process

    @property
    def mixed_precision(self) -> str:
        return self.state.mixed_precision

    @property
    def use_distributed(self) -> bool:
        return self.state.use_distributed

    @property
    def project_dir(self):
        return self.project_configuration.project_dir

    @property
    def logging_dir(self):
        return self.project_configuration.logging_dir

    @property
    def save_iteration(self):
        return self.project_configuration.iteration

    @property
    def sync_gradients(self) -> bool:
        return self.gradient_state.sync_gradients

    @property
    def gradient_accumulation_steps(self) -> int:
        return self.gradient_state.num_steps

    def __repr__(self):
        return repr(self.state._partial) + f"Mixed precision: {self.mixed_precision}\n"

    # ------------------------------------------------------------------ process control
    def wait_for_everyone(self):
        self.state.wait_for_everyone()

    def print(self, *args, **kwargs):
        self.state._partial.print(*args, **kwargs)

    def on_main_process(self, function):
        return self.state._partial.on_main_process(function)

    def on_local_main_process(self, function):
        return self.state._partial.on_local_main_process(function)

    def on_process(self, function=None, process_index=None):
        return self.state._partial.on_process(function, process_index)

    @contextlib.contextmanager
    def main_process_first(self):
        with self.state._partial.main_process_first():
            yield

    @contextlib.contextmanager
    def local_main_process_first(self):
        with self.state._partial.local_main_process_first():
            yield

    def split_between_processes(self, inputs, apply_padding: bool = False):
        return self.state._partial.split_between_processes(inputs, apply_padding=apply_padding)

    # ------------------------------------------------------------------ accumulation
    def _do_sync(self):
        """Decide whether this step is a sync boundary (reference accelerator.py:999)."""
        if self.gradient_state.sync_with_dataloader and self.gradient_state.end_of_dataloader:
            self.step = 0
            self.gradient_state._set_sync_gradients(True)
        else:
            self.step += 1
            self.gradient_state._set_sync_gradients((self.step % self.gradient_state.num_steps) == 0)

    @contextlib.contextmanager
    def accumulate(self, *models):
        """Gradient-accumulation context (reference accelerator.py:1024-1058).

        Under GSPMD there is no DDP `no_sync` to enter — skipping the cross-replica
        reduction while accumulating falls out of *not applying* the optimizer update;
        per-microbatch grads stay resident as sharded device arrays.
        """
        self._do_sync()
        yield

    @contextlib.contextmanager
    def no_sync(self, model=None):
        """Parity shim (reference accelerator.py:909-948): forces the next `step()` to
        skip; gradient reduction cost is already deferred under GSPMD."""
        prev = self.gradient_state.sync_gradients
        self.gradient_state._set_sync_gradients(False)
        try:
            yield
        finally:
            self.gradient_state._set_sync_gradients(prev)

    @contextlib.contextmanager
    def join_uneven_inputs(self, joinables, even_batches=None):
        """Parity shim for torch's DDP Join (reference accelerator.py:1060-1131): under
        jit-stable shapes + even_batches padding there are no uneven inputs to join."""
        if even_batches is not None:
            logger.warning("join_uneven_inputs(even_batches=...) is advisory here; padding is handled by the loader")
        yield

    # ------------------------------------------------------------------ prepare
    def prepare(self, *args, device_placement=None):
        """Prepare models/optimizers/dataloaders/schedulers in one call
        (reference accelerator.py:1180). Order-independent; schedulers bind to the
        prepared optimizers in a second pass (reference two-pass at :1163)."""
        if device_placement is None:
            device_placement = [None] * len(args)
        elif not isinstance(device_placement, (list, tuple)):
            device_placement = [device_placement] * len(args)

        # Peek at co-prepared dataloaders BEFORE placing models: the MPMD
        # pipeline planner sizes its microbatch schedule off the global batch,
        # and a schedule planned for the wrong batch fails loudly at step time
        # (mpmd.py's split guard) instead of training on wrong gradients.
        for obj in args:
            if self._is_dataloader(obj):
                bs = (
                    getattr(obj, "total_batch_size", None)
                    or getattr(obj, "batch_size", None)
                    or getattr(getattr(obj, "batch_sampler", None), "batch_size", None)
                )
                if bs:
                    self._planning_batch_hint = int(bs)
                    break

        first_pass = []
        for obj, dp in zip(args, device_placement):
            if self._is_model(obj):
                first_pass.append(self.prepare_model(obj))
            elif self._is_optimizer(obj):
                first_pass.append(obj)  # bound after models exist
            elif self._is_dataloader(obj):
                first_pass.append(self.prepare_data_loader(obj, device_placement=dp))
            else:
                first_pass.append(obj)

        result = []
        for obj in first_pass:
            if self._is_optimizer(obj):
                result.append(self.prepare_optimizer(obj))
            else:
                result.append(obj)

        final = []
        for obj in result:
            if self._is_scheduler(obj):
                final.append(self.prepare_scheduler(obj))
            else:
                final.append(obj)
        return final[0] if len(final) == 1 else tuple(final)

    @staticmethod
    def _is_model(obj) -> bool:
        from .parallel.mpmd import MPMDPipelinedModel
        from .parallel.pipeline import PipelinedModel

        return isinstance(obj, (Model, PreparedModel, PipelinedModel, MPMDPipelinedModel))

    @staticmethod
    def _is_optimizer(obj) -> bool:
        if isinstance(obj, AcceleratedOptimizer):
            return True
        return hasattr(obj, "init") and hasattr(obj, "update") and not hasattr(obj, "apply")

    @staticmethod
    def _is_dataloader(obj) -> bool:
        if isinstance(obj, (DataLoaderShard, DataLoaderDispatcher, SimpleDataLoader)):
            return True
        from .native.loader import NativeArrayLoader

        if isinstance(obj, NativeArrayLoader):
            return True
        try:
            import torch.utils.data

            if isinstance(obj, torch.utils.data.DataLoader):
                return True
        except ImportError:
            pass
        return False

    @classmethod
    def _is_scheduler(cls, obj) -> bool:
        if isinstance(obj, AcceleratedScheduler):
            return True
        if cls._is_model(obj) or cls._is_optimizer(obj) or cls._is_dataloader(obj):
            return False
        # optax schedules are bare callables step->lr; or any object with get_last_lr()
        return (callable(obj) and not isinstance(obj, type) and not hasattr(obj, "init")) or hasattr(
            obj, "get_last_lr"
        )

    def prepare_model(self, model: Union[Model, PreparedModel], device_placement=None, evaluation_mode=False):
        """Place a model on the mesh with derived shardings
        (reference prepare_model accelerator.py:1316)."""
        from .parallel.mpmd import MPMDPipelinedModel
        from .parallel.pipeline import PipelinedModel

        if isinstance(model, (PreparedModel, PipelinedModel, MPMDPipelinedModel)):
            # Already placed (pipeline models are stage-sharded at construction).
            if model not in self._models:
                self._models.append(model)
            return model
        from .parallel.sharding import derive_param_shardings

        mesh = self.mesh
        fsdp = self.state.fsdp_plugin
        if (
            fsdp is not None
            and fsdp.sync_module_states
            and self.num_processes > 1
            and not evaluation_mode
        ):
            # Reference FSDP sync_module_states (accelerator.py:1431+): rank 0's
            # initial weights win, so per-host random init or racy loads can't
            # diverge the replicas. Runs on host arrays before placement.
            from .utils.operations import broadcast

            model.params = broadcast(model.params, from_process=0)
        if isinstance(model.sharding_rules, str):
            # sharding_rules="auto": the cost-model planner searches the
            # MODEL-axis (tensor-parallel) layout for this mesh and emits the
            # rules table every consumer below (param/opt-state derivation)
            # already reads. The planner owns only the "model" axis here:
            # "fsdp" sharding stays the deriver's job — the fsdp_plugin is
            # the user's explicit memory request, spec_for_param extends the
            # planner's rules with the fsdp dim exactly as it extends the
            # hand tables (Megatron+ZeRO composition), and overriding that
            # from a cost model that can't see the real batch would silently
            # undo a policy the user set on purpose. The resolved table
            # replaces the sentinel on the bundle so the optimizer's mirrored
            # derivation sees the same rules, not the string.
            from .parallel.planner import Workload, resolve_sharding_rules

            if model.sharding_rules == "rules":
                raise ValueError(
                    "sharding_rules='rules' is a serving-engine sentinel (it "
                    "means 'fall back to the Model bundle's family table'); on "
                    "this seam the bundle's sharding_rules IS that table, and "
                    "the sentinel just overwrote it — leave the table in place, "
                    "or pass 'auto' for the planner"
                )
            adam_bytes = 8.0  # fp32 moments; the dominant non-param account
            # Training meshes add the "data" axis to the search: the planner
            # then enumerates ZeRO twins (optimizer moments sharded along
            # "data" even where params replicate) and emits them as a second
            # rules table the optimizer derivation consumes.
            mesh_sizes = dict(getattr(mesh, "shape", {}) or {})
            if mesh_sizes.get("pipeline", 1) > 1:
                # 3-axis mesh: plan-and-place the MPMD pipeline executor. The
                # planner byte-balances the layers onto the "pipeline" axis
                # (assignments may be NON-uniform), emits a full 2D rules +
                # ZeRO opt-rules pair PER STAGE submesh, and the runtime
                # places each stage by its own tables — the prepared object
                # is an MPMDPipelinedModel whose step comes from
                # `Accelerator.train_step`, not a single-mesh PreparedModel.
                from .models import layered_for_model
                from .parallel.planner import plan_mpmd_train_sharding

                # Settings the single-mesh route honors must not be dropped
                # silently here (same explicit-rejection style as train_step's
                # loss_fn/max_grad_norm): ZeRO weight-update sharding already
                # rides the per-stage opt-rules tables, but the fsdp param/
                # grad knobs and the fp8 recipe have no per-stage twin yet.
                if fsdp is not None:
                    raise NotImplementedError(
                        "fsdp_plugin is not supported on the MPMD pipeline "
                        "route: stage params shard by the per-stage planner "
                        "tables, not the fsdp wrap policy. Drop the plugin "
                        "(ZeRO optimizer-state sharding is planned per stage "
                        "automatically) or use a 2-axis mesh."
                    )
                if self.state.mixed_precision == "fp8":
                    raise NotImplementedError(
                        "mixed_precision='fp8' is not supported on the MPMD "
                        "pipeline route (no per-stage fp8 recipe); use 'bf16' "
                        "or a 2-axis mesh."
                    )
                mp_dtype = None
                if self.state.mixed_precision in ("bf16", "fp16"):
                    mp_dtype = self.state.compute_dtype
                mp_autocast = True
                if self.autocast_handler is not None and not self.autocast_handler.enabled:
                    mp_autocast = False
                # Size the microbatch schedule off the real global batch when a
                # dataloader was prepared in the same call — a schedule divided
                # for the wrong batch can't split the step (mpmd.py raises).
                plan_batch = self._planning_batch_hint or 8
                layered = layered_for_model(model)
                prelude, layers, tail = layered.split(model.params)
                mpmd_plan = plan_mpmd_train_sharding(
                    prelude,
                    layers,
                    tail,
                    mesh,
                    batch=plan_batch,
                    seq=512,
                    opt_bytes_per_param=adam_bytes,
                )
                pipelined = MPMDPipelinedModel(
                    model,
                    layered,
                    mesh,
                    mpmd_plan,
                    compute_dtype=mp_dtype,
                    autocast=mp_autocast,
                )
                self._models.append(pipelined)
                return pipelined
            plan_axes = tuple(
                a for a in ("data", "model") if mesh_sizes.get(a, 1) > 1
            ) or ("model",)
            rules, _plan = resolve_sharding_rules(
                model.sharding_rules,
                model.params,
                mesh,
                plan_kwargs=dict(
                    axes=plan_axes,
                    workload=Workload(batch=8, seq=512, opt_bytes_per_param=adam_bytes),
                ),
            )
            model.sharding_rules = rules
            if _plan is not None and getattr(_plan, "opt_rules", None):
                model.opt_sharding_rules = list(_plan.opt_rules)
        param_sharding = derive_param_shardings(
            model.params, mesh, fsdp_plugin=fsdp, rules=model.sharding_rules
        )
        compute_dtype = None
        autocast = True
        if self.autocast_handler is not None and not self.autocast_handler.enabled:
            autocast = False
        if self.state.mixed_precision in ("bf16", "fp16", "fp8"):
            compute_dtype = self.state.compute_dtype
        fp8_recipe = None
        if self.state.mixed_precision == "fp8":
            fp8_recipe = self.fp8_recipe_handler or FP8RecipeKwargs()
        # Activation checkpointing: the CompilationConfig policy (expert knob)
        # wins; the FSDP boolean maps to classic full per-layer remat.
        remat_policy = self.compilation_config.remat_policy
        if remat_policy is None and fsdp is not None and fsdp.activation_checkpointing:
            remat_policy = "full"
        prepared = PreparedModel(
            model,
            mesh=mesh,
            param_sharding=param_sharding,
            compute_dtype=compute_dtype,
            autocast=autocast,
            fp8_recipe=fp8_recipe,
            offload_params=bool(getattr(fsdp, "offload_params", False)),
            param_dtype=getattr(fsdp, "param_dtype", None),
            reduce_dtype=getattr(fsdp, "reduce_dtype", None),
            remat_policy=remat_policy,
        )
        self._models.append(prepared)
        return prepared

    def prepare_optimizer(self, optimizer, device_placement=None, model=None) -> AcceleratedOptimizer:
        """Bind an optax transformation to the (single) prepared model
        (reference prepare_optimizer accelerator.py:2011)."""
        if isinstance(optimizer, AcceleratedOptimizer):
            if optimizer not in self._optimizers:
                self._optimizers.append(optimizer)
            return optimizer
        if model is None:
            if len(self._models) == 0:
                raise ValueError(
                    "Prepare the model before (or together with) the optimizer: the optimizer "
                    "state is sharded like the parameters it updates."
                )
            model = self._models[-1]
        prepared = AcceleratedOptimizer(
            optimizer,
            model=model,
            scaler=self.scaler,
            mesh=self.mesh,
            fsdp_plugin=self.state.fsdp_plugin,
        )
        self._optimizers.append(prepared)
        return prepared

    def prepare_data_loader(self, data_loader, device_placement=None, slice_fn_for_dispatch=None):
        """(reference prepare_data_loader accelerator.py:1958)"""
        if isinstance(data_loader, (DataLoaderShard, DataLoaderDispatcher)):
            if data_loader not in self._dataloaders:
                self._dataloaders.append(data_loader)
            return data_loader
        if device_placement is None:
            device_placement = self.device_placement
        cfg = self.dataloader_config
        prepared = prepare_data_loader(
            data_loader,
            split_batches=cfg.split_batches or self.split_batches,
            put_on_device=device_placement,
            rng_types=self.rng_types.copy(),
            dispatch_batches=cfg.dispatch_batches,
            even_batches=cfg.even_batches,
            slice_fn_for_dispatch=slice_fn_for_dispatch,
            use_seedable_sampler=cfg.use_seedable_sampler,
            prefetch_size=cfg.prefetch_size,
        )
        self._dataloaders.append(prepared)
        return prepared

    def prepare_scheduler(self, scheduler) -> AcceleratedScheduler:
        """(reference prepare_scheduler accelerator.py:2052)"""
        if isinstance(scheduler, AcceleratedScheduler):
            if scheduler not in self._schedulers:
                self._schedulers.append(scheduler)
            return scheduler
        prepared = AcceleratedScheduler(
            scheduler,
            self._optimizers,
            step_with_optimizer=self.step_scheduler_with_optimizer,
            split_batches=self.dataloader_config.split_batches or self.split_batches,
        )
        self._schedulers.append(prepared)
        # Order-independent with train_step(steps_per_call=K): whichever comes
        # second surfaces the coarsening.
        k = getattr(self, "_last_steps_per_call", 1)
        if k > 1:
            self._warn_scheduler_coarsened(k)
        return prepared

    def _warn_scheduler_coarsened(self, steps_per_call: int):
        """A scheduler's contract is one LR update per optimizer step; the
        scanned device loop reads the LR override ONCE per compiled call, so
        K>1 coarsens the schedule to K-step strides (documented in
        train_step.py's docstring; this surfaces it at prepare time instead of
        leaving it to be discovered from a training curve)."""
        logger.warning(
            "train_step(steps_per_call=%d) with a prepared scheduler: the LR is "
            "read once per compiled call, so the scheduler advances in %d-step "
            "strides instead of per optimizer step. Use steps_per_call=1 for an "
            "exact per-step schedule, or step the scheduler once per call.",
            steps_per_call,
            steps_per_call,
        )

    # ------------------------------------------------------------------ backward
    def _resolve_model(self, model) -> PreparedModel:
        if model is not None:
            return model
        if len(self._models) == 1:
            return self._models[0]
        raise ValueError("Multiple prepared models: pass model= to backward()/clip_grad_norm_().")

    def _optimizer_for(self, model: PreparedModel) -> AcceleratedOptimizer:
        for opt in self._optimizers:
            if opt.model is model:
                return opt
        raise ValueError("No prepared optimizer bound to this model.")

    def backward(self, loss_fn: Callable, *args, model: Optional[PreparedModel] = None, **kwargs):
        """Compute gradients of `loss_fn(params, *args, **kwargs)` and accumulate them
        into the bound optimizer; returns the (unscaled, fp32) loss value.

        The reference divides the loss by the accumulation count (accelerator.py:2115)
        and lets autograd run — here the same scaling happens inside one jitted
        value_and_grad whose gradient pytree inherits the parameter shardings, so the
        reduce-scatter/psum over ("data","fsdp") is fused into the backward by XLA.
        """
        model = self._resolve_model(model)
        if getattr(model, "is_mpmd", False):
            raise NotImplementedError(
                "backward() computes one single-mesh grad pytree; an MPMD "
                "pipeline model's gradients live per stage on per-stage "
                "submeshes. Use step_fn = accelerator.train_step() — it runs "
                "the 1F1B schedule with per-stage accumulation and updates."
            )
        optimizer = self._optimizer_for(model)
        # Key on the underlying function object (held strongly by the dict), not id():
        # bound methods like `model.loss` are re-created per access (id churn → retrace),
        # and a freed function's id can be reused (silent stale-closure hit).
        key = (getattr(loss_fn, "__func__", loss_fn), id(model))
        if key not in self._backward_cache:
            import jax

            # Optional PreparedModel protocol, same guard as optimizer.py:289 /
            # train_step.py:105 — duck-typed models need not implement offload.
            to_compute = getattr(model, "to_compute_memory", lambda p: p)

            def _compute(params, scale, *fargs, **fkwargs):
                # Host-offloaded params stream to device memory OUTSIDE the grad
                # closure so gradients come out device-resident.
                params = to_compute(params)

                def scaled(p):
                    out = loss_fn(p, *fargs, **fkwargs)
                    loss, aux = out if isinstance(out, tuple) else (out, None)
                    return loss * scale, (loss, aux)

                grads, (loss, aux) = jax.grad(scaled, has_aux=True)(params)
                return grads, loss, aux

            self._backward_cache[key] = jax.jit(_compute)
        import jax.numpy as jnp

        scale = 1.0 / self.gradient_state.num_steps
        if self.scaler is not None and self.scaler.enabled:
            scale = scale * self.scaler.scale
        grads, loss, aux = self._backward_cache[key](model.params, jnp.asarray(scale, jnp.float32), *args, **kwargs)
        optimizer.accumulate_grads(grads)
        if aux is not None:
            return loss, aux
        return loss

    def train_step(
        self,
        loss_fn: Optional[Callable] = None,
        *,
        model: Optional[PreparedModel] = None,
        max_grad_norm: Optional[float] = None,
        accumulation_steps: Optional[int] = None,
        steps_per_call: int = 1,
    ):
        """Build the fused per-step program: ONE jitted call doing
        value_and_grad + (clip) + optimizer update with donated params/opt-state,
        with `lax.scan` microbatch accumulation when `accumulation_steps > 1`.

        `steps_per_call=K > 1` additionally scans K FULL optimizer steps inside
        the one program (pass a batch stacking K step-batches along dim 0); host
        dispatch cost is paid once per K steps — the device-training-loop mode
        for small-step configs, whose device step is short beside the host's
        per-call dispatch.

        This is the TPU performance path; `backward()`/`optimizer.step()` remain as
        the eager-feel compatibility surface (reference accelerator.py:2093-2121).

        Usage::

            step_fn = accelerator.train_step(max_grad_norm=1.0)
            for batch in loader:
                loss = step_fn(batch)
                scheduler.step()

        `accumulation_steps` defaults to the Accelerator's
        `gradient_accumulation_steps`; in that mode pass one batch pytree whose
        arrays stack the microbatches along dim 0 (`[k*b, ...]`).
        """
        from .train_step import FusedTrainStep

        model = self._resolve_model(model)
        optimizer = self._optimizer_for(model)
        if getattr(model, "is_mpmd", False):
            # MPMD pipeline route: the model already owns its per-stage
            # programs and optimizer states; the step IS the 1F1B schedule
            # (microbatch accumulation is built in — accumulation_steps and
            # loss_fn/max_grad_norm knobs belong to the single-mesh fused
            # step and are rejected rather than silently ignored).
            if loss_fn is not None or max_grad_norm is not None or steps_per_call != 1:
                raise NotImplementedError(
                    "MPMD pipeline training uses the model's logits-level loss "
                    "and per-stage updates; loss_fn=, max_grad_norm= and "
                    "steps_per_call= are not supported on this route."
                )
            step = model.make_train_step(optimizer.tx)
            if self.trace_guard is not None:
                step = self.trace_guard.wrap(step, warmup=2)
            return self._instrument_step(step)
        if accumulation_steps is None:
            accumulation_steps = self.gradient_state.num_steps
        # Latest build wins (not a ratchet): rebuilding with K=1 after a K>1
        # experiment must not leave a stale warning armed for a scheduler
        # prepared later.
        self._last_steps_per_call = steps_per_call
        if steps_per_call > 1 and self._schedulers:
            self._warn_scheduler_coarsened(steps_per_call)
        step = FusedTrainStep(
            model,
            optimizer,
            loss_fn=loss_fn,
            max_grad_norm=max_grad_norm,
            accumulation_steps=accumulation_steps,
            gradient_state=self.gradient_state,
            steps_per_call=steps_per_call,
            tracer=self.tracer,
        )
        if self.trace_guard is not None:
            # analyze mode: steady-state steps must neither recompile nor make
            # guarded host transfers. warmup=2 because the first scheduler step
            # installing an lr override legitimately rebuilds the with_lr
            # program once (train_step.py's _jitted cache).
            step = self.trace_guard.wrap(step, warmup=2)
        return self._instrument_step(step)

    def _instrument_step(self, step_fn: Callable) -> Callable:
        """Telemetry shim around the fused step: each call is timed as the
        timeline's "dispatch" phase (host enqueue — pure perf_counter
        arithmetic, no device sync), wrapped in a `train.step` span, heartbeats
        the hang watchdog, and polls the ProfilerManager + flight recorder so
        touch-file / SIGUSR2 capture and trace-dump requests are served at
        step boundaries. The wait for the step's batch, which the loader whose
        pass the step runs in has stamped, becomes the timeline's "data_wait"
        phase afterwards: a loader opens no step, so a pass that feeds no
        `train_step()` (an evaluation) is nobody's step. Exceptions (including
        TraceGuardViolation from analyze mode) propagate untouched."""
        timeline, profiler = self.timeline, self.profiler
        tracer, recorder = self.tracer, self.tracer.recorder
        gradient_state = self.gradient_state
        counter = {"step": 0}

        def instrumented(*args, **kwargs):
            counter["step"] += 1
            with timeline.phase("dispatch"), tracer.span(
                "train.step", category="train", step=counter["step"]
            ):
                out = step_fn(*args, **kwargs)
            timeline.step_done()
            loader = gradient_state.active_dataloader
            waited = getattr(loader, "data_wait_s", None)
            if waited is not None:
                loader.data_wait_s = None  # one step a batch takes it
                timeline.record_phase("data_wait", waited)
            recorder.heartbeat()
            profiler.poll()
            recorder.poll()
            return out

        instrumented.__wrapped__ = step_fn  # type: ignore[attr-defined]
        guard = getattr(step_fn, "trace_guard", None)
        if guard is not None:
            instrumented.trace_guard = guard  # type: ignore[attr-defined]
        return instrumented

    def clip_grad_norm_(self, parameters=None, max_norm: float = 1.0, norm_type: int = 2, model=None):
        """Clip accumulated grads by global norm; no-op while accumulating
        (reference accelerator.py:2221)."""
        if not self.sync_gradients:
            return None
        if norm_type != 2:
            raise NotImplementedError("Only the L2 global norm is supported")
        model = self._resolve_model(model)
        return self._optimizer_for(model).clip_grad_norm_(max_norm)

    def clip_grad_value_(self, parameters=None, clip_value: float = 1.0, model=None):
        if not self.sync_gradients:
            return
        model = self._resolve_model(model)
        self._optimizer_for(model).clip_grad_value_(clip_value)

    # ------------------------------------------------------------------ collectives
    def gather(self, tensor):
        """(reference accelerator.py:2299)"""
        return ops.gather(tensor)

    def gather_for_metrics(self, input_data, use_gather_object: bool = False):
        """Gather with duplicate-tail truncation on the final batch
        (reference accelerator.py:2331-2396)."""
        try:
            all_tensors = all(ops.is_array_like(t) for t in (
                input_data.values() if isinstance(input_data, dict) else
                (input_data if isinstance(input_data, (list, tuple)) else [input_data])
            ))
        except TypeError:
            all_tensors = False
        if use_gather_object or not all_tensors:
            data = ops.gather_object(input_data if isinstance(input_data, list) else [input_data])
        else:
            data = ops.gather(input_data)

        if self.gradient_state.end_of_dataloader:
            remainder = self.gradient_state.remainder
            if remainder is not None and remainder > 0:
                if use_gather_object or not all_tensors:
                    return data[:remainder]

                def _truncate(t):
                    return t[:remainder]

                return ops.recursively_apply(_truncate, data)
        return data

    def reduce(self, tensor, reduction="sum", scale=1.0):
        return ops.reduce(tensor, reduction=reduction, scale=scale)

    def pad_across_processes(self, tensor, dim=0, pad_index=0, pad_first=False):
        return ops.pad_across_processes(tensor, dim=dim, pad_index=pad_index, pad_first=pad_first)

    # ------------------------------------------------------------------ trigger
    def set_trigger(self):
        """Set a cross-process breakpoint flag (reference accelerator.py:2127)."""
        self.flag_tensor = np.array([1], dtype=np.int64)

    def check_trigger(self) -> bool:
        """True if any process called set_trigger (reference accelerator.py:2153)."""
        flag = self.flag_tensor if self.flag_tensor is not None else np.array([0], dtype=np.int64)
        total = ops.reduce(flag, reduction="sum")
        if int(np.asarray(total)[0]) >= 1:
            self.flag_tensor = None
            return True
        return False

    # ------------------------------------------------------------------ preemption
    def register_preemption_checkpoint(self, output_dir: Optional[str] = None, exit_on_save: bool = True):
        """Install a SIGTERM latch (TPU-VM preemption); `check_preemption()` then
        saves full state at the next step boundary (SURVEY §5: the elastic/preemption
        machinery the reference delegates to torchrun).

        `output_dir` is a `CheckpointManager` BASE directory: each preemption save
        commits an atomically-published `checkpoint_N` inside it, so a hard kill
        racing the save can never leave a torn checkpoint, and resume via
        `load_state(output_dir)` (or `"latest"` under automatic naming) lands on
        the newest checkpoint that digest-verifies. Off the main thread the latch
        degrades to a warn + no-op (the `signal` module's restriction) instead of
        crashing the caller."""
        from .fault_tolerance import PreemptionHandler

        self._preemption_handler = PreemptionHandler()
        self._preemption_dir = output_dir
        self._preemption_exit = exit_on_save
        return self._preemption_handler

    @property
    def preemption_requested(self) -> bool:
        handler = getattr(self, "_preemption_handler", None)
        return handler is not None and handler.preemption_requested

    def check_preemption(self) -> bool:
        """Call at step boundaries: on a latched SIGTERM, saves state (to the
        registered dir or the project checkpoint dir) and exits 143. Returns False
        when training should continue."""
        if not self.preemption_requested:
            return False
        from .fault_tolerance import PREEMPTED_EXIT_CODE

        # Flush the in-flight async commit BEFORE the preemption save: the
        # handoff must not leave a background commit racing process exit. A
        # commit that FAILED is logged, not raised — the preemption checkpoint
        # about to be written supersedes it.
        try:
            self.drain_checkpoints()
        except CheckpointCommitError as exc:
            logger.warning(
                "in-flight async checkpoint commit failed during preemption flush "
                "(%s); the preemption checkpoint will supersede it", exc,
            )
        preemption_dir = getattr(self, "_preemption_dir", None)
        if preemption_dir is not None and not self.project_configuration.automatic_checkpoint_naming:
            # The registered dir is a manager base: numbered, rotated, atomically
            # committed — the supervisor can SIGKILL us mid-save and the previous
            # checkpoint stays loadable.
            manager = CheckpointManager(preemption_dir, keep_last_n=2)
            path = manager.save(
                manager.next_step(),
                lambda staging: self._write_state_artifacts(staging, None, self.sharded_save),
                is_main=self.is_main_process,
                barrier=self.wait_for_everyone,
                manifest_extra=sharded_manifest_extra(self.num_processes)
                if self.sharded_save
                else None,
            )
        else:
            # ALWAYS synchronous: the process exits right after this save, and
            # an async commit would race its own death.
            path = self.save_state(preemption_dir, async_save=False)
        self.print(f"preemption checkpoint saved to {path}")
        if getattr(self, "_preemption_exit", True):
            raise SystemExit(PREEMPTED_EXIT_CODE)
        return True

    # ------------------------------------------------------------------ profiling
    @contextlib.contextmanager
    def profile(self, log_dir: Optional[str] = None):
        """Capture an XLA device trace for the wrapped block, via the
        `telemetry.ProfilerManager` (which also serves on-demand touch-file /
        SIGUSR2 captures between these scoped ones — docs/observability.md).
        Output is an xplane dump viewable in TensorBoard / xprof / Perfetto."""
        manager = self.profiler
        if log_dir is not None or not manager.enabled:
            if log_dir is None:
                base = self.logging_dir or self.project_dir or "."
                log_dir = os.path.join(str(base), "profile")
            # Scoped capture outside the launch-configured dir: a transient
            # manager sharing this Accelerator's registry (instruments are
            # get-or-create, so capture counts keep accumulating in one place).
            manager = ProfilerManager(log_dir=str(log_dir), registry=self.telemetry)
        with manager.trace():
            yield
        self.wait_for_everyone()

    def save_memory_profile(self, path: str):
        """Dump a device-memory (HBM) profile in pprof format."""
        if self.is_main_process:
            manager = self.profiler if self.profiler.enabled else ProfilerManager(
                log_dir=os.path.dirname(os.path.abspath(path)) or ".", registry=self.telemetry
            )
            manager.save_memory_snapshot(path)

    # ------------------------------------------------------------------ precision
    @contextlib.contextmanager
    def autocast(self, autocast_handler: Optional[AutocastKwargs] = None):
        """Toggle the compute-dtype policy for forwards inside the context
        (reference accelerator.py:3292). Jit caches are cleared on toggle."""
        handler = autocast_handler or AutocastKwargs()
        previous = [(m, m.autocast_enabled) for m in self._models]
        for m in self._models:
            if m.autocast_enabled != handler.enabled and m.compute_dtype is not None:
                m.autocast_enabled = handler.enabled
                m._jit_cache.pop("apply", None)
        try:
            yield
        finally:
            for m, prev in previous:
                if m.autocast_enabled != prev:
                    m.autocast_enabled = prev
                    m._jit_cache.pop("apply", None)

    # ------------------------------------------------------------------ model access
    def unwrap_model(self, model, keep_fp32_wrapper: bool = True):
        """(reference accelerator.py:2598 → utils extract_model_from_parallel)"""
        from .utils.other import extract_model_from_parallel

        return extract_model_from_parallel(model, keep_fp32_wrapper)

    def free_memory(self, *objects):
        """Release prepared objects + compiled executables (reference accelerator.py:3128)."""
        import gc

        import jax

        self._models.clear()
        self._optimizers.clear()
        self._schedulers.clear()
        self._dataloaders.clear()
        self._backward_cache.clear()
        self._last_steps_per_call = 1
        self.step = 0
        objects = list(objects)
        for i in range(len(objects)):
            objects[i] = None
        gc.collect()
        jax.clear_caches()
        return objects

    def clear(self, *objects):
        return self.free_memory(*objects)

    # ------------------------------------------------------------------ trackers
    def init_trackers(self, project_name: str, config: Optional[dict] = None, init_kwargs: dict = None):
        """(reference accelerator.py:2611)"""
        init_kwargs = init_kwargs or {}
        self.trackers = []
        for tracker in self.log_with:
            if isinstance(tracker, GeneralTracker):
                self.trackers.append(tracker)
                continue
            tracker_cls = LOGGER_TYPE_TO_CLASS[str(tracker)]
            kwargs = init_kwargs.get(str(tracker), {})
            if tracker_cls.requires_logging_directory:
                self.trackers.append(tracker_cls(project_name, self.logging_dir, **kwargs))
            else:
                self.trackers.append(tracker_cls(project_name, **kwargs))
        if config is not None:
            for tracker in self.trackers:
                tracker.store_init_configuration(config)

    def get_tracker(self, name: str, unwrap: bool = False):
        for tracker in self.trackers:
            if tracker.name == name:
                return tracker.tracker if unwrap else tracker
        raise ValueError(f"No tracker named {name} is running")

    def log(self, values: dict, step: Optional[int] = None, log_kwargs: dict = None):
        """Fan out metrics to every tracker (reference accelerator.py:2639)."""
        log_kwargs = log_kwargs or {}
        for tracker in self.trackers:
            tracker.log(values, step=step, **log_kwargs.get(tracker.name, {}))

    def end_training(self):
        """(reference accelerator.py:2678). Also the shutdown barrier for async
        checkpointing: the last async commit must land (or surface its failure)
        before the run is declared over."""
        self.drain_checkpoints()
        for tracker in self.trackers:
            tracker.finish()
        self.wait_for_everyone()

    # ------------------------------------------------------------------ checkpoint
    def register_for_checkpointing(self, *objects):
        """Track extra objects in save_state/load_state (reference accelerator.py:3256)."""
        invalid = [obj for obj in objects if not (hasattr(obj, "state_dict") and hasattr(obj, "load_state_dict"))]
        if invalid:
            raise ValueError(
                f"Objects must expose state_dict/load_state_dict; got invalid: {[type(o).__name__ for o in invalid]}"
            )
        self._custom_objects.extend(objects)

    def register_save_state_pre_hook(self, hook: Callable):
        self._save_model_hooks.append(hook)

    def register_load_state_pre_hook(self, hook: Callable):
        self._load_model_hooks.append(hook)

    def checkpoint_manager(self, base_dir: Optional[str] = None) -> CheckpointManager:
        """The crash-safe checkpoint store for this run: rooted at the project's
        `checkpoints/` dir (or an explicit base), rotating to `total_limit`.

        Memoized per (base_dir, keep_last_n): the manager's in-flight-step
        registry is what makes `next_step()` race-safe against a background
        committer, and that registry only protects callers sharing the SAME
        instance — a fresh manager per save_state would never see the step a
        previous call's commit still has staged."""
        if base_dir is None:
            if self.project_dir is None:
                raise ValueError("checkpoint_manager needs a project_dir or an explicit base_dir")
            base_dir = os.path.join(self.project_dir, "checkpoints")
        key = (str(base_dir), self.project_configuration.total_limit)
        cache = getattr(self, "_checkpoint_managers", None)
        if cache is None:
            cache = self._checkpoint_managers = {}
        if key not in cache:
            cache[key] = CheckpointManager(base_dir, keep_last_n=key[1])
        return cache[key]

    def _write_state_artifacts(
        self, output_dir: str, save_model_kwargs: Optional[dict] = None, sharded: bool = False
    ):
        """Write every state artifact into `output_dir` (all processes). The
        caller owns directory-level atomicity/commit. `sharded=True` routes
        through the snapshot writer so each process lands only its addressable
        shards in its own `host_*/` subdirectory."""
        for hook in self._save_model_hooks:
            hook(self._models, None, output_dir)

        rng_key = self._models[0]._rng if self._models else None
        if sharded:
            snapshot = snapshot_accelerator_state(
                self._models,
                self._optimizers,
                self._schedulers,
                self._dataloaders,
                rng_key=rng_key,
                sharded=True,
                custom_objects=tuple(self._custom_objects),
            )
            write_accelerator_snapshot(
                snapshot,
                output_dir,
                process_index=self.process_index,
                num_processes=self.num_processes,
                is_main=self.is_main_process,
                save_on_each_node=self.project_configuration.save_on_each_node,
            )
            return
        save_accelerator_state(
            output_dir,
            self._models,
            self._optimizers,
            self._schedulers,
            self._dataloaders,
            rng_key=rng_key,
            save_on_each_node=self.project_configuration.save_on_each_node,
            state_dict_type=getattr(self.state.fsdp_plugin, "state_dict_type", None)
            or "SHARDED_STATE_DICT",
        )
        for i, obj in enumerate(self._custom_objects):
            if self.is_main_process:
                save_custom_state(obj, output_dir, i)

    def maybe_save_state(self, output_dir: Optional[str] = None, **save_kwargs) -> Optional[str]:
        """Step-boundary checkpoint driver for the `save_interval` cadence:
        call once per training step; it times the step gap, asks the
        controller whether a save is due, and — when it is — runs
        `save_state()` and feeds the controller the goodput ledger's measured
        blocking cost (for `save_interval="auto"`, that measurement is what
        sets the NEXT interval against the `lost_checkpoint_s` budget).
        Returns the checkpoint path when a save ran, else None."""
        if self.save_controller is None:
            raise RuntimeError(
                "maybe_save_state() needs a cadence: construct the Accelerator with "
                'save_interval="auto" (goodput-driven) or save_interval=<steps>'
            )
        now = time.perf_counter()
        if self._last_step_boundary is not None:
            self.save_controller.observe_step(now - self._last_step_boundary)
        self._last_step_boundary = now
        self._steps_since_save += 1
        if not self.save_controller.should_save(self._steps_since_save):
            return None
        charged_before = self.timeline.goodput()["lost_s"].get("checkpoint", 0.0)
        t0 = time.perf_counter()
        path = self.save_state(output_dir, **save_kwargs)
        blocked = time.perf_counter() - t0
        charged = self.timeline.goodput()["lost_s"].get("checkpoint", 0.0) - charged_before
        # The ledger's charge IS the blocking cost (async saves charge only
        # snapshot+barrier); fall back to the local wall clock if a custom
        # timeline did not record one.
        self.save_controller.observe_save(charged if charged > 0 else blocked)
        self._steps_since_save = 0
        self._last_step_boundary = time.perf_counter()  # save time is not step time
        return path

    def save_state(
        self,
        output_dir: Optional[str] = None,
        async_save: Optional[bool] = None,
        sharded: Optional[bool] = None,
        **save_model_kwargs,
    ) -> str:
        """Save everything prepared + registered (reference accelerator.py:2830).

        With `automatic_checkpoint_naming`, commits
        `{project_dir}/checkpoints/checkpoint_{iteration}` through
        `CheckpointManager`: artifacts stage in a hidden temp dir, a per-file
        SHA-256 manifest is written, the directory is renamed into place
        atomically, the `latest` pointer advances, and rotation keeps
        `total_limit`. A kill at ANY byte offset leaves only committed
        checkpoints visible. An explicit `output_dir` writes in place (each
        artifact individually atomic) and finishes with the digest manifest so
        `load_state` can verify it.

        `async_save`/`sharded` override the Accelerator-level knobs per call.
        An async save blocks only for the device->host snapshot (plus a barrier
        on the previous commit if it is still in flight); the atomic commit
        pipeline runs on a background thread, its wall-clock lands in
        `checkpoint_async_commit_seconds` (a `checkpoint.commit` span) instead
        of the goodput ledger, and a FAILED commit surfaces as
        `CheckpointCommitError` on the next save/`drain_checkpoints()` — never
        silently dropped. The returned path is where the checkpoint WILL
        publish; call `drain_checkpoints()` before reading it."""
        async_save = self.async_save if async_save is None else bool(async_save)
        sharded = self.sharded_save if sharded is None else bool(sharded)
        if async_save:
            return self._save_state_async(output_dir, sharded, **save_model_kwargs)
        t0 = time.perf_counter()
        try:
            with self.tracer.span(
                "checkpoint.save", category="checkpoint", step=int(self.save_iteration)
            ):
                result = self._save_state_inner(output_dir, sharded=sharded, **save_model_kwargs)
        finally:
            # Goodput ledger: checkpoint saves are wall clock the run paid that
            # was not a training step (docs/observability.md) — charged even
            # when the save fails (failed-save time is still lost time).
            self.timeline.charge("checkpoint", time.perf_counter() - t0)
        # Completion instruments bump only on SUCCESS: a raised save must not
        # look like a usable checkpoint on a dashboard.
        self._m_ckpt_saves.inc()
        self._m_ckpt_seconds.observe(time.perf_counter() - t0)
        return result

    def _save_state_inner(
        self, output_dir: Optional[str] = None, sharded: bool = False, **save_model_kwargs
    ) -> str:
        if self.project_configuration.automatic_checkpoint_naming:
            manager = self.checkpoint_manager()
            logger.info(
                "Saving current state to %s (checkpoint_%d)", manager.base_dir, self.save_iteration
            )
            output_dir = manager.save(
                self.save_iteration,
                lambda staging: self._write_state_artifacts(staging, save_model_kwargs, sharded),
                is_main=self.is_main_process,
                barrier=self.wait_for_everyone,
                manifest_extra=sharded_manifest_extra(self.num_processes) if sharded else None,
            )
            self.project_configuration.iteration += 1
            return output_dir
        if output_dir is None:
            raise ValueError("output_dir is required when automatic_checkpoint_naming is off")
        self.wait_for_everyone()
        os.makedirs(output_dir, exist_ok=True)
        logger.info("Saving current state to %s", output_dir)
        self._write_state_artifacts(output_dir, save_model_kwargs, sharded)
        self.wait_for_everyone()  # every process's artifacts land before the digest scan
        if self.is_main_process:
            write_checkpoint_manifest(
                output_dir, extra=sharded_manifest_extra(self.num_processes) if sharded else None
            )
        self.project_configuration.iteration += 1
        return output_dir

    # ------------------------------------------------------------------ async checkpointing
    def _committer(self) -> AsyncCommitter:
        if self._async_committer is None:
            self._async_committer = AsyncCommitter()
        return self._async_committer

    def _save_state_async(
        self, output_dir: Optional[str], sharded: bool, **save_model_kwargs
    ) -> str:
        """Snapshot-then-commit: the train loop pays only for (a) a barrier on
        the PREVIOUS commit when it is still in flight and (b) the device->host
        state snapshot; serialize+fsync+atomic-publish run on the background
        committer. Only the blocking portion charges the goodput ledger."""
        if self.num_processes > 1 and not sharded:
            raise ValueError(
                "async_save with num_processes > 1 requires sharded=True: the background "
                "committer cannot run collective barriers, so cross-host commits "
                "coordinate through the per-host shard sentinels"
            )
        t0 = time.perf_counter()
        committer = self._committer()
        step = int(self.save_iteration)
        try:
            with self.tracer.span(
                "checkpoint.save", category="checkpoint", step=step, mode="async"
            ):
                # The barrier: the previous async commit must finish before its
                # successor snapshots (one in-flight commit bounds host memory),
                # and ITS failure surfaces here instead of being dropped.
                committer.wait()
                if self._save_model_hooks:
                    logger.warning(
                        "async_save runs registered save-state hooks on the committer "
                        "thread against live objects; use synchronous saves if a hook "
                        "reads state that training mutates"
                    )
                rng_key = self._models[0]._rng if self._models else None
                snapshot = snapshot_accelerator_state(
                    self._models,
                    self._optimizers,
                    self._schedulers,
                    self._dataloaders,
                    rng_key=rng_key,
                    sharded=sharded,
                    custom_objects=tuple(self._custom_objects),
                )
                if self.project_configuration.automatic_checkpoint_naming:
                    manager = self.checkpoint_manager()
                    final = os.path.join(manager.base_dir, f"checkpoint_{step}")

                    def writer(abort):
                        manager.save(
                            step,
                            lambda staging: self._commit_snapshot(staging, snapshot, abort),
                            is_main=self.is_main_process,
                            abort=abort,
                            manifest_extra=sharded_manifest_extra(self.num_processes)
                            if sharded
                            else None,
                        )
                else:
                    if output_dir is None:
                        raise ValueError(
                            "output_dir is required when automatic_checkpoint_naming is off"
                        )
                    final = str(output_dir)

                    def writer(abort):
                        os.makedirs(final, exist_ok=True)
                        self._commit_snapshot(final, snapshot, abort)
                        if self.is_main_process:
                            write_checkpoint_manifest(
                                final,
                                extra=sharded_manifest_extra(self.num_processes)
                                if sharded
                                else None,
                            )

                self.project_configuration.iteration += 1
        finally:
            # Only the BLOCKING portion is goodput-lost step time; the
            # background commit reports through checkpoint_async_commit_seconds.
            blocking = time.perf_counter() - t0
            self.timeline.charge("checkpoint", blocking)
        self._m_ckpt_seconds.observe(blocking)
        logger.info("Async save of step %d accepted; committing to %s in background", step, final)

        def timed_commit(abort):
            c0 = time.perf_counter()
            self._g_ckpt_in_flight.set(1)
            try:
                with self.tracer.span(
                    "checkpoint.commit", category="checkpoint", step=step, mode="async"
                ):
                    writer(abort)
            finally:
                self._g_ckpt_in_flight.set(0)
                self._m_ckpt_commit_seconds.observe(time.perf_counter() - c0)
            self._m_ckpt_saves.inc()  # success only, like the sync path

        committer.submit(timed_commit, label=f"checkpoint_{step}")
        return final

    def _commit_snapshot(self, output_dir: str, snapshot: dict, abort=None):
        """Committer-thread artifact writer: save hooks (live objects — see the
        async_save warning) + the snapshot serialization."""
        for hook in self._save_model_hooks:
            hook(self._models, None, output_dir)
        write_accelerator_snapshot(
            snapshot,
            output_dir,
            process_index=self.process_index,
            num_processes=self.num_processes,
            is_main=self.is_main_process,
            save_on_each_node=self.project_configuration.save_on_each_node,
            abort=abort,
        )

    def drain_checkpoints(self, timeout: Optional[float] = None):
        """Barrier on the in-flight async commit. Raises `CheckpointCommitError`
        if it failed — the failure-surfacing contract's shutdown edge: call
        before reading a just-saved checkpoint, at end of training, or before a
        preemption handoff."""
        if self._async_committer is not None:
            self._async_committer.drain(timeout)

    def poll_async_checkpoint(self):
        """Non-blocking: re-raise a process-death-class failure (an injected
        kill, KeyboardInterrupt) from the background committer. Ordinary commit
        failures keep to the barrier contract and surface at the next
        save/drain. Call at step boundaries (chaos and supervised loops do)."""
        if self._async_committer is not None:
            self._async_committer.poll()

    def abort_async_checkpoint(self, timeout: float = 30.0):
        """Hard shutdown: abort the in-flight commit (it will NOT publish) and
        join without raising. Returns the commit's stored failure, if any. The
        committer is single-use after an abort; the next async save builds a
        fresh one."""
        committer, self._async_committer = self._async_committer, None
        if committer is None:
            return None
        return committer.abort_and_join(timeout)

    def load_state(self, input_dir: Optional[str] = None, **load_model_kwargs):
        """(reference accelerator.py:2995)

        `input_dir` may be: a concrete checkpoint directory (digest-verified when
        it carries a manifest), a `CheckpointManager` base directory or the
        literal `"latest"` / `None` (with `automatic_checkpoint_naming`) — both
        resolve to the newest checkpoint that VERIFIES, falling back past a
        corrupted newest one to the last good save."""
        t0 = time.perf_counter()
        try:
            with self.tracer.span("checkpoint.load", category="checkpoint"):
                result = self._load_state_inner(input_dir, **load_model_kwargs)
        finally:
            # Restart-recovery time (resume after a preemption/crash respawn)
            # charges the goodput ledger's "restart" cause; the supervisor-side
            # downtime is `fault_tolerance.Supervisor.downtime_s`.
            self.timeline.charge("restart", time.perf_counter() - t0)
        self._m_ckpt_loads.inc()  # completions only, like saves
        return result

    def _load_state_inner(self, input_dir: Optional[str] = None, **load_model_kwargs):
        # A resume in the same process as an async save must see the commit
        # land (or fall back past it) — resolve() before the drain could miss
        # the newest checkpoint. A FAILED commit downgrades to a warning: the
        # whole point of resolve() is falling back to the last good save.
        try:
            self.drain_checkpoints()
        except CheckpointCommitError as exc:
            logger.warning("async commit failed before load_state (%s); resolving past it", exc)
        if input_dir == "latest":
            input_dir = None
        if input_dir is None:
            if not self.project_configuration.automatic_checkpoint_naming and self.project_dir is None:
                raise ValueError("input_dir is required when automatic_checkpoint_naming is off")
            input_dir = self.checkpoint_manager().resolve()
        else:
            input_dir = str(input_dir)
            if CheckpointManager.is_manager_dir(input_dir):
                # A manager base (e.g. a preemption checkpoint root): newest
                # verified checkpoint inside it.
                input_dir = CheckpointManager(input_dir).resolve()
            else:
                input_dir = self.checkpoint_manager(os.path.dirname(input_dir) or ".").resolve(input_dir)
        if self.project_configuration.automatic_checkpoint_naming:
            # Resume numbering after the restored checkpoint so the next save_state
            # doesn't collide with an existing directory.
            nums = re.findall(r"(\d+)(?=[^\/]*$)", str(input_dir))
            if nums:
                self.project_configuration.iteration = int(nums[0]) + 1
        logger.info("Loading states from %s", input_dir)

        for hook in self._load_model_hooks:
            hook(self._models, input_dir)

        if is_sharded_checkpoint_dir(input_dir):
            # Per-host sharded checkpoint: gather-on-load assembles each tree
            # from every host's shard files, then placement re-shards onto the
            # CURRENT mesh — the same code path restores a pod checkpoint on
            # its own topology or on a single recovery host.
            rng_key = load_sharded_accelerator_state(
                input_dir, self._models, self._optimizers, self._schedulers, self._dataloaders
            )
        else:
            rng_key = load_accelerator_state(
                input_dir, self._models, self._optimizers, self._schedulers, self._dataloaders
            )
        if rng_key is not None and self._models:
            self._models[0]._rng = rng_key
        for i, obj in enumerate(self._custom_objects):
            load_custom_state(obj, input_dir, i)

    def save_model(
        self,
        model: PreparedModel,
        save_directory: str,
        safe_serialization: bool = True,
        max_shard_size="5GB",
    ):
        """Export just the weights (reference save_model accelerator.py:2691).

        `safe_serialization=True` (default) writes (sharded) safetensors with an
        HF-style index via `save_model_safetensors` — parameters stream to host
        one tensor at a time, so a fully-sharded model never gathers whole.
        `FullyShardedDataParallelPlugin.state_dict_type` picks the multi-host
        behavior: FULL_STATE_DICT allgathers non-addressable params per-tensor
        and writes one logical state dict from the main process;
        SHARDED_STATE_DICT (default) keeps non-addressable params distributed
        and writes per-shard via orbax/tensorstore (the
        torch.distributed.checkpoint equivalent, reference utils/fsdp_utils.py:85).
        """
        from .checkpointing import _all_addressable, save_model_safetensors, save_pytree, save_sharded

        if not isinstance(safe_serialization, bool):
            # HF-reference positional order, save_model(model, dir, max_shard_size,
            # safe_serialization): a non-bool third argument is a shard size from
            # code ported off the reference — honor it instead of silently
            # truth-testing a string.
            shard_size = safe_serialization
            safe_serialization = max_shard_size if isinstance(max_shard_size, bool) else True
            max_shard_size = shard_size
        os.makedirs(save_directory, exist_ok=True)
        params = model.state_dict()
        if not safe_serialization:
            if self.is_main_process:
                save_pytree(params, os.path.join(save_directory, "model.npz"))
            return
        state_dict_type = getattr(self.state.fsdp_plugin, "state_dict_type", None) or "FULL_STATE_DICT"
        if not _all_addressable(params) and state_dict_type == "SHARDED_STATE_DICT":
            save_sharded(params, os.path.join(save_directory, "model.sharded"))
            return
        save_model_safetensors(params, save_directory, max_shard_size=max_shard_size)

    def skip_first_batches(self, dataloader, num_batches: int = 0):
        """(reference accelerator.py:3274)"""
        return skip_first_batches(dataloader, num_batches)
