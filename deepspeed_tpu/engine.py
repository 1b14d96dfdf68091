"""Training engine (ref: deepspeed/runtime/engine.py DeepSpeedEngine +
deepspeed/__init__.py initialize).

The reference engine wraps a torch module and orchestrates an imperative
loop: forward → backward (hooked for ZeRO reduce) → step (optimizer with
loss-scale checks), with micro-batch accumulation counted by host-side
bookkeeping.  The TPU-native engine compiles ONE SPMD program per train
step: grad accumulation is a ``lax.scan`` over microbatches, ZeRO is a set
of shardings (:mod:`deepspeed_tpu.zero`), loss scaling and clipping run
inside the jit, and buffers are donated so params/optimizer state update
in place in HBM.

DeepSpeed's three-call idiom is preserved::

    loss = engine(batch)        # computes the whole step, defers commit
    engine.backward(loss)       # no-op (bwd already fused into the step)
    engine.step()               # commits the new state

alongside the native ``loss = engine.train_batch(batch)``.
"""

from __future__ import annotations

import time
from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from deepspeed_tpu import lr_schedules, precision, zero
from deepspeed_tpu.config import Config
from deepspeed_tpu.devprof import BuildCounters, ProgramSpan
from deepspeed_tpu.ops.optim import Optimizer, from_config as opt_from_config
from deepspeed_tpu.telemetry import MetricsRegistry
from deepspeed_tpu.topology import MeshSpec, default_mesh, set_current_mesh
from deepspeed_tpu.utils.logging import logger


class TrainState(NamedTuple):
    """Replicated-control training state; leaf shardings carry ZeRO."""

    step: jnp.ndarray          # i32
    params: Any                # master params (master_dtype)
    opt_state: Any
    scaler: precision.ScalerState


def _is_init_thunk(params: Any) -> bool:
    """True iff ``params`` is a zero-arg init thunk (zero.Init parity)
    rather than a parameter pytree.  A bare callable (function, lambda,
    partial) is a pytree LEAF; a callable container (an equinox-style
    module that flattens into array children) is eager params."""
    return callable(params) and jax.tree_util.treedef_is_leaf(
        jax.tree.structure(params))


def accum_split(batch: Any, accum: int, dp_world: int) -> Any:
    """[B, ...] → [accum, B/accum, ...] microbatch split with NO
    cross-device movement.

    A naive reshape takes CONTIGUOUS row blocks as microbatches, which
    under a data-sharded batch makes XLA all-gather the whole batch onto
    every device (measured: +2 all-gathers per step at dp=8 accum=4,
    see ACCUM_AUDIT.json / tools/accum_reshard_audit.py).  Any partition
    of rows into microbatches is an equally valid accumulation split —
    the accumulated gradient is the mean over ALL rows either way — so
    split each device's LOCAL rows instead: view [dp, accum, mb_local],
    swap to microbatch-major.  The sharded leading dim is only
    relabeled, and XLA compiles the whole split to zero collectives.
    """
    def f(x):
        B = x.shape[0]
        if dp_world <= 1 or B % (dp_world * accum):
            # undersized/odd batches (smaller than the configured global
            # batch) keep the naive split — correctness over comms
            return x.reshape((accum, B // accum) + x.shape[1:])
        mb = B // (dp_world * accum)
        y = x.reshape((dp_world, accum, mb) + x.shape[1:])
        y = jnp.swapaxes(y, 0, 1)
        return y.reshape((accum, B // accum) + x.shape[1:])

    return jax.tree.map(f, batch)


def global_norm(tree: Any) -> jnp.ndarray:
    leaves = [jnp.sum(jnp.square(l.astype(jnp.float32)))
              for l in jax.tree.leaves(tree)]
    return jnp.sqrt(jnp.sum(jnp.stack(leaves)))


def clip_by_global_norm(tree: Any, max_norm: float) -> Tuple[Any, jnp.ndarray]:
    """ref: deepspeed/runtime/utils.py clip_grad_norm_.

    The factor multiply preserves each leaf's dtype: an f32 scalar times
    a bf16 tree would type-promote the WHOLE tree to f32 — a transient
    full-size copy that defeats bf16-grad memory budgets (the norm
    itself is still accumulated in f32 by global_norm)."""
    norm = global_norm(tree)
    factor = jnp.minimum(1.0, max_norm / (norm + 1e-6))
    return jax.tree.map(
        lambda g: g * factor.astype(g.dtype), tree), norm


class TrainingEngine:
    """One jitted SPMD train step + host-side bookkeeping.

    Parameters
    ----------
    loss_fn: ``(params, batch) -> loss`` or ``(params, batch) -> (loss, aux)``.
        ``params`` arrive cast to the compute dtype (bf16 by default).
    params: initial master parameter pytree (will be cast to master dtype
        and placed according to the ZeRO stage's shardings).
    config: parsed :class:`~deepspeed_tpu.config.Config`.
    mesh: :class:`~deepspeed_tpu.topology.MeshSpec`; default built from
        ``config.mesh`` over all devices.
    param_specs: optional model-parallel (TP) shardings — a pytree of
        PartitionSpec matching params, or a callable ``leaf -> spec``;
        ZeRO layers the data axis on top of these.
    """

    def __init__(self, loss_fn: Callable, params: Any, config: Config,
                 mesh: Optional[MeshSpec] = None,
                 optimizer: Optional[Optimizer] = None,
                 lr_scheduler=None,
                 param_specs: "zero.SpecTree" = None,
                 has_aux: bool = False):
        # the registry first, because the build is measured: the
        # counters take every program made ready from here to the end
        # of _finish_init and again around the first step
        # (deepspeed_tpu.devprof); the spans are entered in place
        self.registry = MetricsRegistry(enabled=config.telemetry.enabled)
        self._build: Optional[BuildCounters] = BuildCounters(self.registry)
        self._sp_build_state = self.registry.span(
            "build_state", "the state program: parameters and optimizer "
            "state placed on the mesh")
        self._sp_build_step = ProgramSpan(self.registry.span(
            "build_step", "the first dstpu_train_step dispatch"))(
                "train_step")
        self.config = config
        self.mesh = mesh or MeshSpec.build(
            config.mesh.axis_sizes(jax.device_count()))
        # publish for model-side sharded ops (ring/ulysses attention, MoE)
        from deepspeed_tpu import topology as _topo

        _topo.set_current_mesh(self.mesh)
        config.resolve_batch_sizes(self.mesh.dp_world)
        self.loss_fn = loss_fn
        self.has_aux = has_aux
        self.param_specs = param_specs
        stage = config.zero.stage

        # ---- compressed-communication mode (ref: onebit optimizers +
        # ZeRO++ qgZ).  Resolved BEFORE the optimizer is built so a 1-bit
        # optimizer gets the bound axis name when the compressed shard_map
        # step will actually run.
        from deepspeed_tpu import comm_compress

        self.grad_comm_mode = comm_compress.resolve_mode(
            config, self.mesh,
            optimizer.name if optimizer is not None else config.optimizer.type,
            has_aux)
        if self.grad_comm_mode == "onebit" and config.gradient_clipping > 0:
            logger.warning(
                "gradient_clipping is ignored under the 1-bit optimizer "
                "path (the exact global grad never exists; the reference "
                "has the same semantics)")
        if self.grad_comm_mode == "onebit" and optimizer is not None and \
                optimizer.axis_name != comm_compress.AXIS:
            raise ValueError(
                "user-supplied 1-bit optimizer must be built with "
                f"axis_name={comm_compress.AXIS!r} to run in the engine's "
                "compressed step (yours has "
                f"axis_name={optimizer.axis_name!r}, which would do NO "
                "cross-device communication and silently diverge); or "
                "omit `optimizer=` and configure it via the config dict")

        # ---- optimizer + schedule (ref: engine._configure_optimizer)
        from deepspeed_tpu.ops.optim import default_lr

        opt_lr = float(config.optimizer.params.get(
            "lr", default_lr(config.optimizer.type)))
        self.lr_schedule = (
            lr_scheduler if callable(lr_scheduler)
            else lr_schedules.from_config(config.scheduler.type,
                                          config.scheduler.params,
                                          fallback_lr=opt_lr))
        if optimizer is None:
            oparams = dict(config.optimizer.params)
            oparams["lr"] = self.lr_schedule
            if self.grad_comm_mode == "onebit":
                oparams["axis_name"] = comm_compress.AXIS
            optimizer = opt_from_config(config.optimizer.type, oparams)
        if self.grad_comm_mode == "onebit":
            # per-device error feedback lives in engine state as a
            # [world, ...] stack; each device owns its slice via a
            # P("data") sharding on the leading dim.
            import dataclasses as _dc

            W = self.mesh.size("data")
            base_init = optimizer.init

            def stacked_init(p):
                st = base_init(p)
                return st._replace(err=jax.tree.map(
                    lambda e: jnp.zeros((W,) + e.shape, e.dtype), st.err))

            optimizer = _dc.replace(optimizer, init=stacked_init)
        self.optimizer = optimizer

        # ---- state layout: ZeRO shardings
        mdt = precision.master_dtype(config.precision)
        # zero.Init parity (ref: deepspeed/runtime/zero/partition_parameters
        # .py Init): ``params`` may be a zero-arg init thunk.  Shardings are
        # derived from ``eval_shape`` and the thunk runs INSIDE the jitted
        # state init with sharded out_shardings, so the full parameter tree
        # is never materialized unsharded on any one device.  Only a bare
        # callable counts — a callable pytree CONTAINER (e.g. an equinox-
        # style module whose treedef has children) is still eager params.
        params_thunk = None
        if _is_init_thunk(params):
            params_thunk = params
            params = jax.eval_shape(params_thunk)
        cast_dt = lambda dt: mdt if jnp.issubdtype(dt, jnp.floating) else dt
        if params_thunk is not None:
            params = jax.tree.map(
                lambda s: jax.ShapeDtypeStruct(s.shape, cast_dt(s.dtype)),
                params)
            self._cast_thunk = lambda: jax.tree.map(
                lambda p: p.astype(cast_dt(p.dtype)) if
                jnp.issubdtype(p.dtype, jnp.floating) else p, params_thunk())
        else:
            self._cast_thunk = None
            params = jax.tree.map(
                lambda p: jnp.asarray(p, cast_dt(jnp.asarray(p).dtype)),
                params)
        if self.grad_comm_mode == "qwz":
            if config.zero.offload_param or config.zero.offload_optimizer:
                raise ValueError(
                    "zero_quantized_weights does not compose with offload "
                    "(the flat-shard step owns the param layout); use the "
                    "scheduled Infinity engine or drop the qwZ flag")
            self._setup_qwz_state(params, mdt)
            return self._finish_init()
        self.param_shardings = zero.param_shardings(
            params, self.mesh, stage, param_specs)
        opt_state_shape = jax.eval_shape(self.optimizer.init, params)
        self.opt_shardings = zero.optstate_shardings(
            opt_state_shape, params, self.mesh, stage, param_specs)
        if self.grad_comm_mode == "onebit":
            from jax.sharding import PartitionSpec as _P

            self.opt_shardings = self.opt_shardings._replace(
                err=jax.tree.map(
                    lambda _: self.mesh.sharding(_P("data")), params))
        if config.zero.offload_optimizer or config.zero.offload_param:
            from deepspeed_tpu.offload import engine_offload_shardings

            self.param_shardings, self.opt_shardings = \
                engine_offload_shardings(config, self.param_shardings,
                                         self.opt_shardings)
        repl = self.mesh.replicated()
        self.state_shardings = TrainState(
            step=repl, params=self.param_shardings,
            opt_state=self.opt_shardings,
            scaler=precision.ScalerState(repl, repl))

        def dstpu_make_state(p):
            return TrainState(
                step=jnp.zeros([], jnp.int32),
                params=p,
                opt_state=self.optimizer.init(p),
                scaler=precision.scaler_init(config.precision))

        with self._sp_build_state:
            if self._cast_thunk is not None:
                cast_thunk, self._cast_thunk = self._cast_thunk, None
                self.state = jax.jit(
                    lambda: dstpu_make_state(cast_thunk()),
                    out_shardings=self.state_shardings)()
            else:
                self.state = jax.jit(
                    dstpu_make_state,
                    out_shardings=self.state_shardings)(params)
        self._finish_init()

    def _finish_init(self) -> None:
        """Shared __init__ tail: compile the step fns, host bookkeeping."""
        config = self.config
        # ---- the compiled step.  The batch sharding (a pytree prefix — one
        # NamedSharding broadcast to every leaf) splits the batch dim over
        # the data axes so each chip receives only its slice.
        batch_sharding = self.mesh.sharding(self.mesh.batch_spec())
        self._batch_sharding = batch_sharding
        # batch placement happens in _align_batch (device_put per leaf, so
        # scalar batch fields ride along replicated); in_shardings=None
        # respects those committed placements without re-transfer
        # a stable program name: a capture's "XLA Modules" line shows
        # jit_dstpu_train_step
        def dstpu_train_step(state, batch):
            try:
                return self._train_step(state, batch)
            finally:
                # the ZeRO stage is a fact of this trace: a model's
                # forward outside an engine must not meet it (the mesh
                # stays for the ring, Ulysses and MoE readers)
                set_current_mesh(self.mesh)

        self._step_fn = jax.jit(
            dstpu_train_step,
            in_shardings=(self.state_shardings, None),
            out_shardings=(self.state_shardings, None),
            donate_argnums=(0,))
        self._eval_fn = jax.jit(self._eval_step,
                                in_shardings=(self.state_shardings, None))

        # curriculum (ref: engine.curriculum_scheduler +
        # megatron curriculum_seqlen truncation in the train path): the
        # parsed block must DRIVE the step, not sit inert — seqlen-type
        # curricula truncate the batch's sequence axis before the jit.
        # difficulty_step quantization bounds the distinct compiled
        # shapes, exactly the reference's recompile-limiting knob.
        self.curriculum_scheduler = None
        if config.curriculum is not None and config.curriculum.enabled:
            from deepspeed_tpu.data.curriculum import CurriculumScheduler

            self.curriculum_scheduler = CurriculumScheduler(config.curriculum)
        # PLD / eigenvalue engine attributes (ref: the reference engine
        # owns progressive_layer_drop and eigenvalue objects; models read
        # theta / keep-probs from here, _post_step advances the schedule)
        self.progressive_layer_drop = None
        if config.progressive_layer_drop:
            from deepspeed_tpu.runtime_extras import ProgressiveLayerDrop

            pld = config.progressive_layer_drop
            self.progressive_layer_drop = ProgressiveLayerDrop(
                theta=float(pld.get("theta", 0.5)),
                gamma=float(pld.get("gamma", 0.001)))
        self.eigenvalue = None
        if config.eigenvalue:
            from deepspeed_tpu.runtime_extras import Eigenvalue

            ev = config.eigenvalue
            self.eigenvalue = Eigenvalue(
                max_iter=int(ev.get("max_iter", 100)),
                tol=float(ev.get("tol", 1e-2)),
                stability=float(ev.get("stability", 1e-6)))

        # host bookkeeping (ref: engine.global_steps / skipped_steps)
        self.global_steps = 0
        self._pending: Optional[dict] = None
        self._last_metrics = {}
        # monitoring + throughput (ref: engine._configure_monitoring +
        # ThroughputTimer in engine.train).  Backends come straight from the
        # reference's config keys (tensorboard/wandb/csv_monitor).
        from deepspeed_tpu.monitor import MonitorMaster
        from deepspeed_tpu.timers import ThroughputTimer

        self.monitor = MonitorMaster(config.raw)
        self.tput_timer = ThroughputTimer(batch_size=config.train_batch_size)
        # unified telemetry (the `telemetry` config block): step-timing
        # histogram + run gauges on a MetricsRegistry, with the optional
        # exporter bridging into the monitor backends / a Prometheus
        # file on a wall-clock cadence.  Default posture keeps the hot
        # path sync-free: gauges that require a device sync (loss, grad
        # norm, MFU) refresh only on the steps_per_print cadence when a
        # sink will read them, or on demand via telemetry_snapshot().
        from deepspeed_tpu.telemetry import TelemetryExporter

        tel = config.telemetry
        self._c_train_steps = self.registry.counter(
            "train_steps", "optimizer steps taken")
        # spans built once (telemetry.Span: histogram + TraceAnnotation;
        # dstpu/train_step and dstpu/train_align_batch in a capture)
        self._sp_step = self.registry.span(
            "train_step",
            "per-step wall time (host dispatch wall unless "
            "telemetry.step_sync — then device-synced via the "
            "ThroughputTimer)",
            buckets=(0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1,
                     0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0))
        self._sp_align = self.registry.span(
            "train_align_batch",
            "placing the host batch on the mesh before the step")
        self._g_loss = self.registry.gauge("train_loss")
        self._g_lr = self.registry.gauge("train_lr")
        self._g_grad_norm = self.registry.gauge("train_grad_norm")
        self._g_sps = self.registry.gauge(
            "train_samples_per_sec", "ThroughputTimer samples/sec")
        self._g_mfu = self.registry.gauge(
            "train_mfu", "model FLOPs utilization vs chip peak "
            "(0 until flops_per_sample is configured)")
        self._tel_sync = tel.enabled and tel.step_sync
        # ---- comm wire observability (hierarchical + quantized
        # collectives, the `comm` config block).  Payload bytes are
        # ANALYTIC device truth, not estimates: the gradient tree's
        # size is static, so every step moves exactly the bytes the
        # schedule says (deepspeed_tpu/comm/collectives.py
        # wire_bytes_per_device).  comm_collective_seconds is observed
        # only at HOST-DRIVEN collective sites (serving placement, ZI
        # layer upload, the bench) — in-jit collective time is
        # read from a device trace, not guessed here.
        self._comm_hier = None
        self._comm_wire = None
        self._comm_overlap = 0.0
        if self.grad_comm_mode in ("qgz", "qwz"):
            import numpy as _np

            from deepspeed_tpu.comm import collectives as _hcoll

            cc = config.comm
            self._comm_hier = _hcoll.resolve_hierarchy(
                self.mesh.size("data"), cc.hierarchy_size,
                devices=self.mesh.mesh.devices.reshape(-1))
            n_elems = sum(
                int(_np.prod(l.shape)) if getattr(l, "ndim", 0) else 1
                for l in jax.tree.leaves(self.state.params))
            # qwZ's int8 gather + reduce-scatter pair is exactly an
            # all-reduce split in two, so one accounting covers both
            codec = cc.codec if self.grad_comm_mode == "qgz" else "group"
            self._comm_wire = _hcoll.wire_bytes_per_device(
                n_elems, self._comm_hier, bits=cc.bits, codec=codec)
            be = _hcoll.bucket_elems_for(
                cc.bucket_mb, self.mesh.size("data"), codec)
            if be and self.grad_comm_mode == "qgz":
                nb = max(1, -(-n_elems // be))
                # scheduling upper bound: all but the first bucket's
                # collective can hide under the next bucket's compute;
                # the measured value is COMM_BENCH's to stamp
                self._comm_overlap = 1.0 - 1.0 / nb if nb > 1 else 0.0
            self._c_comm_int8 = self.registry.counter(
                "comm_bytes_on_wire_int8",
                "per-device int8 payload bytes shipped by the "
                "gradient/weight collectives (analytic, per step)")
            self._c_comm_f32 = self.registry.counter(
                "comm_bytes_on_wire_f32",
                "per-device f32 bytes on the comm wire: quantization "
                "scales, or the whole payload under codec=exact")
            self._g_comm_ratio = self.registry.gauge(
                "comm_compression_ratio",
                "flat-f32 wire bytes / actual wire bytes for one step's "
                "gradient exchange (>= 3.5 is the COMM_BENCH gate)")
            self._g_comm_overlap = self.registry.gauge(
                "comm_bucket_overlap_efficiency",
                "fraction of collective time the bucketed schedule can "
                "hide under compute (scheduling upper bound 1 - 1/n_"
                "buckets; 0 when bucketing is off)")
            self._h_comm_sec = self.registry.histogram(
                "comm_collective_seconds",
                "wall seconds per host-driven collective (placement / "
                "upload paths; in-jit collectives are not observed here)",
                buckets=(0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005,
                         0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5,
                         5.0))
            self._g_comm_ratio.set(self._comm_wire["ratio_vs_f32"])
            self._g_comm_overlap.set(self._comm_overlap)
        self._tel_exporter = None
        if tel.enabled and (tel.prometheus_path or tel.http_port
                            is not None or (tel.monitor_bridge
                                            and self.monitor.enabled)):
            self._tel_exporter = TelemetryExporter(
                self.registry,
                monitor=self.monitor if tel.monitor_bridge else None,
                prometheus_path=tel.prometheus_path,
                interval_s=tel.interval_s, http_port=tel.http_port)
            if self._comm_wire is not None:
                # re-assert the comm gauges on the exporter tick so
                # /historyz rings and incident detectors sample them
                # even when no step has refreshed gauges recently
                self._tel_exporter.register_tick_hook(
                    self._comm_tick, interval_s=1.0, name="comm_sample")
        # overflow count, accumulated as a device scalar so the hot loop
        # never syncs; materialized on read via the skipped_steps property.
        self._skipped_acc = jnp.zeros([], jnp.int32)
        self._skipped_base = 0
        logger.info(
            "TrainingEngine: zero=%d mesh=%s micro=%d accum=%d global=%d "
            "dtype=%s comm=%s",
            config.zero.stage, self.mesh.sizes,
            config.train_micro_batch_size_per_gpu,
            config.gradient_accumulation_steps, config.train_batch_size,
            config.precision.dtype, self.grad_comm_mode or "exact")
        # the first train_batch attaches the counters again
        self._build.built()

    # ------------------------------------------------------- qwZ flat state
    def _setup_qwz_state(self, params, mdt) -> None:
        """ZeRO++ qwZ layout (ref zero_quantized_weights): master params as
        ONE flat ``[world, chunk]`` f32 buffer, each data-axis device
        owning a row.  The step all-gathers the rows as int8(+scales) to
        rebuild compute-dtype model leaves, so the param collective
        carries ~1/2 the bytes of the bf16 all-gather GSPMD would emit
        for plain stage 3 (and ~1/4 of f32)."""
        import numpy as _np

        from jax.sharding import PartitionSpec as P

        from deepspeed_tpu import comm_compress

        leaves, self._qwz_treedef = jax.tree.flatten(params)
        self._qwz_shapes = [l.shape for l in leaves]
        self._qwz_sizes = [int(_np.prod(l.shape)) if l.ndim else 1
                           for l in leaves]
        total = sum(self._qwz_sizes)
        W = self.mesh.size("data")
        unit = comm_compress._GROUP
        self._qwz_chunk = -(-total // (W * unit)) * unit
        sh = self.mesh.sharding(P("data"))
        repl = self.mesh.replicated()
        flat_shape = (W, self._qwz_chunk)
        opt_shape = jax.eval_shape(
            self.optimizer.init, jax.ShapeDtypeStruct(flat_shape, mdt))
        self.param_shardings = sh
        self.opt_shardings = jax.tree.map(
            lambda x: sh if getattr(x, "ndim", 0) == 2 else repl, opt_shape)
        self.state_shardings = TrainState(
            step=repl, params=sh, opt_state=self.opt_shardings,
            scaler=precision.ScalerState(repl, repl))

        def dstpu_make_state(p):
            flat = self._qwz_flatten(p, mdt).reshape(flat_shape)
            return TrainState(
                step=jnp.zeros([], jnp.int32), params=flat,
                opt_state=self.optimizer.init(flat),
                scaler=precision.scaler_init(self.config.precision))

        with self._sp_build_state:
            if self._cast_thunk is not None:
                # zero.Init thunk: flattening is traced, so the thunk
                # runs inside the jit and lands directly in the [world,
                # chunk] rows.  Drop the reference afterwards — the
                # closure may hold large host-side arrays that must
                # become collectable.
                cast_thunk, self._cast_thunk = self._cast_thunk, None
                self.state = jax.jit(
                    lambda: dstpu_make_state(cast_thunk()),
                    out_shardings=self.state_shardings)()
            else:
                self.state = jax.jit(
                    dstpu_make_state,
                    out_shardings=self.state_shardings)(params)

    def _qwz_flatten(self, tree, dtype):
        """Ravel a params-shaped pytree into the padded flat buffer."""
        leaves = jax.tree.leaves(tree)
        flat = jnp.concatenate([jnp.ravel(l).astype(dtype) for l in leaves])
        pad = self.mesh.size("data") * self._qwz_chunk - flat.shape[0]
        if pad:
            flat = jnp.concatenate([flat, jnp.zeros(pad, dtype)])
        return flat

    def _qwz_unflatten(self, flat, dtype):
        """Flat buffer (unpadded prefix) → params-shaped pytree."""
        out, off = [], 0
        for shape, n in zip(self._qwz_shapes, self._qwz_sizes):
            out.append(flat[off:off + n].reshape(shape).astype(dtype))
            off += n
        return jax.tree_util.tree_unflatten(self._qwz_treedef, out)

    def _qwz_train_step(self, state: TrainState, batch, accum: int):
        """Manual ZeRO-3 with compressed collectives, all under shard_map
        over the data axis: int8 param all-gather (qwZ) → local grads →
        gradient reduce-scatter back to the owner row (int8 all-to-all
        when qgZ is also on, exact psum-scatter otherwise) → elementwise
        optimizer update on the local 1/world shard."""
        from jax.sharding import PartitionSpec as P

        from deepspeed_tpu import comm_compress

        ms = self.mesh
        cfg = self.config
        W = ms.size("data")
        C = self._qwz_chunk
        cdt = precision.compute_dtype(cfg.precision)
        qgz_wire = bool(cfg.zero.zeropp_quantized_gradients)
        clip = cfg.gradient_clipping
        # hpZ-aware row gather: inter-node links carry `inter` int8
        # rows instead of `world` when a hierarchy is configured/
        # detected; bit-exact either way (one quantization, pre-wire)
        gather_row, _hier = comm_compress.make_weight_gather(
            cfg.comm, ms)

        def f(pflat, opt_state, mb):
            row = pflat[0]                          # [C] f32 master shard
            full = gather_row(row)
            params = self._qwz_unflatten(full, cdt)

            def local_gf(p, m):
                loss, g = jax.value_and_grad(
                    lambda pp: self._loss_for(pp, m)[0])(p)
                return g, loss

            grads, loss = comm_compress.accumulate_local_grads(
                local_gf, params, mb, accum)
            gflat = self._qwz_flatten(grads, jnp.float32)     # [W*C]
            if qgz_wire:
                from deepspeed_tpu.ops.quant import quantized_reduce_scatter

                gshard = quantized_reduce_scatter(
                    gflat, comm_compress.AXIS,
                    groups_per_shard=C // comm_compress._GROUP)
            else:
                gshard = jax.lax.psum_scatter(
                    gflat, comm_compress.AXIS, scatter_dimension=0,
                    tiled=True) / W
            # global consensus: a nan lands in exactly one owner row
            ok = jax.lax.pmin(
                precision.finite_all(gshard).astype(jnp.int32),
                comm_compress.AXIS).astype(bool)
            # EXACT global norm (unlike 1-bit): grads are fully reduced
            gnorm = jnp.sqrt(jax.lax.psum(
                jnp.sum(jnp.square(gshard)), comm_compress.AXIS))
            if clip > 0:
                gshard = gshard * jnp.minimum(1.0, clip / (gnorm + 1e-6))
            row_of = lambda t: jax.tree.map(
                lambda x: x[0] if getattr(x, "ndim", 0) == 2 else x, t)
            stack = lambda t: jax.tree.map(
                lambda x: x[None] if getattr(x, "ndim", 0) == 1 else x, t)
            opt_local = row_of(opt_state)
            updates, new_opt = self.optimizer.update(gshard, opt_local, row)
            keep = lambda n, o: jax.tree.map(
                lambda a, b: jnp.where(ok, a, b), n, o)
            new_row = keep(row + updates.astype(row.dtype), row)
            new_opt = stack(keep(new_opt, opt_local))
            return (new_row[None], new_opt,
                    jax.lax.pmean(loss, comm_compress.AXIS), gnorm, ok)

        opt_specs = jax.tree.map(
            lambda x: P("data") if getattr(x, "ndim", 0) == 2 else P(),
            state.opt_state)
        new_pflat, new_opt, loss, gnorm, ok = jax.shard_map(
            f, mesh=ms.mesh,
            in_specs=(P("data"), opt_specs,
                      jax.tree.map(lambda _: P("data"), batch)),
            out_specs=(P("data"), opt_specs, P(), P(), P()),
            check_vma=False)(state.params, state.opt_state, batch)
        new_state = TrainState(
            step=state.step + jnp.where(ok, 1, 0).astype(jnp.int32),
            params=new_pflat, opt_state=new_opt, scaler=state.scaler)
        metrics = {"loss": loss, "grad_norm": gnorm,
                   "overflow": (~ok).astype(jnp.int32),
                   "lr": self.lr_schedule(state.step + 1),
                   "loss_scale": state.scaler.scale}
        return new_state, metrics

    # ------------------------------------------------------------------ step
    def _loss_for(self, params, batch):
        # the model's blocks name themselves inside (embed, attn_qkv,
        # flash, mlp, ...): what stays under "loss" alone is the cast
        # of the parameters and the loss proper
        with jax.named_scope("loss"):
            cparams = precision.cast_for_compute(params,
                                                 self.config.precision)
            out = self.loss_fn(cparams, batch)
        if self.has_aux:
            loss, aux = out
        else:
            loss, aux = out, None
        return loss.astype(jnp.float32), aux

    def _train_step(self, state: TrainState, batch):
        # (re)publish the ambient mesh at TRACE time: another engine may
        # have been constructed since __init__, and model code (ring/
        # ulysses attention, MoE, pipeline) reads current_mesh() while
        # tracing this step.  The ZeRO stage goes with it: zero.py's
        # statements inside a model's forward ask for both.
        from deepspeed_tpu import topology as _topo

        cfg = self.config
        _topo.set_current_mesh(self.mesh, zero_stage=cfg.zero.stage)
        # Pipeline mode: the loss fn consumes the WHOLE batch (microbatching
        # happens inside the pipelined scan, ref: runtime/pipe/engine.py
        # train_batch) — no outer accumulation loop.
        accum = 1 if cfg.pipeline.stages > 1 else cfg.gradient_accumulation_steps
        stage = cfg.zero.stage

        def scaled_loss(params, mb):
            loss, aux = self._loss_for(params, mb)
            return precision.scale_loss(loss, state.scaler, cfg.precision), (loss, aux)

        grad_fn = jax.grad(scaled_loss, has_aux=True)

        if self.grad_comm_mode == "onebit":
            return self._onebit_train_step(state, batch, accum)
        if self.grad_comm_mode == "qwz":
            return self._qwz_train_step(state, batch, accum)
        if self.grad_comm_mode == "qgz":
            from deepspeed_tpu import comm_compress

            def local_gf(p, mb):
                g, (loss, _a) = grad_fn(p, mb)
                return g, loss

            # the comm block picks the wire: hierarchy (auto/explicit),
            # codec (blockwise v2 / legacy group / exact), bucketing —
            # all resolved at trace time, flat+blockwise by default
            reduce_fn, _hier = comm_compress.make_reduce_fn(
                cfg.comm, self.mesh)
            grads, loss = comm_compress.local_grad_shardmap(
                local_gf, self.mesh, accum,
                reduce_fn=reduce_fn)(state.params, batch)
            grads = zero.grad_constraint(grads, self.mesh, stage,
                                         self.param_specs)
            _aux = None
            return self._finish_step(state, grads, loss, _aux)

        def micro(carry, mb):
            gacc, lacc = carry
            g, (loss, _aux) = grad_fn(state.params, mb)
            g = zero.grad_constraint(g, self.mesh, stage, self.param_specs)
            gacc = jax.tree.map(lambda a, b: a + b.astype(jnp.float32), gacc, g)
            return (gacc, lacc + loss), _aux

        if accum > 1:
            # [global_batch, ...] -> [accum, micro_global, ...]
            mbatch = accum_split(batch, accum, self.mesh.dp_world)
            zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                                 state.params)
            zeros = zero.grad_constraint(zeros, self.mesh, stage,
                                         self.param_specs)
            (grads, loss_sum), aux_stack = jax.lax.scan(
                micro, (zeros, jnp.float32(0.0)), mbatch)
            grads = jax.tree.map(lambda g: g / accum, grads)
            loss = loss_sum / accum
            _aux = (jax.tree.map(lambda a: jnp.mean(a, axis=0), aux_stack)
                    if self.has_aux else None)
        else:
            grads, (loss, _aux) = grad_fn(state.params, batch)
            grads = zero.grad_constraint(grads, self.mesh, stage, self.param_specs)
            grads = jax.tree.map(lambda g: g.astype(jnp.float32), grads)

        return self._finish_step(state, grads, loss, _aux)

    def _finish_step(self, state: TrainState, grads, loss, _aux):
        """Shared step tail: unscale/overflow-check, clip, update, commit."""
        cfg = self.config
        with jax.named_scope("grad_clip"):
            grads, ok, new_scaler = precision.unscale_and_check(
                grads, state.scaler, cfg.precision)

            if cfg.gradient_clipping > 0:
                grads, gnorm = clip_by_global_norm(
                    grads, cfg.gradient_clipping)
            else:
                gnorm = global_norm(grads)

        with jax.named_scope("optimizer"):
            updates, new_opt = self.optimizer.update(
                grads, state.opt_state, state.params)
            new_params = jax.tree.map(lambda p, u: p + u.astype(p.dtype),
                                      state.params, updates)
            # overflow → skip the update, keep old state (ref:
            # fused_optimizer.step)
            keep = lambda new, old: jax.tree.map(
                lambda n, o: jnp.where(ok, n, o), new, old)
            new_state = TrainState(
                step=state.step + jnp.where(ok, 1, 0).astype(jnp.int32),
                params=keep(new_params, state.params),
                opt_state=keep(new_opt, state.opt_state),
                scaler=new_scaler)
        metrics = {"loss": loss, "grad_norm": gnorm,
                   "overflow": (~ok).astype(jnp.int32),
                   "lr": self.lr_schedule(state.step + 1),
                   "loss_scale": new_scaler.scale}
        if self.has_aux:
            # surface the model's aux outputs (e.g. MoE load/aux losses)
            metrics["aux"] = _aux
        return new_state, metrics

    def _onebit_train_step(self, state: TrainState, batch, accum: int):
        """1-bit optimizer step: the whole grad→compressed-momentum-comm→
        update sequence runs under shard_map over the data axis, so the
        optimizer's int8 sign all-gather is genuinely what crosses the
        wire (ref: deepspeed/runtime/fp16/onebit/adam.py, where the
        optimizer owns communication).

        State contract: mu/nu replicated (identical on every device after
        the shared compressed reduction), err stacked [world, ...] with
        each device owning its slice (P("data") leading dim).
        """
        from jax.sharding import PartitionSpec as P

        from deepspeed_tpu import comm_compress

        ms = self.mesh

        def f(params, opt_state, mb):
            err_local = jax.tree.map(
                lambda e: jnp.squeeze(e, 0), opt_state.err)
            ob = opt_state._replace(err=err_local)

            def local_gf(p, m):
                # bf16/fp32 only (gated at init): no loss scaling
                loss, g = jax.value_and_grad(
                    lambda pp: self._loss_for(pp, m)[0])(p)
                return g, loss

            grads, loss = comm_compress.accumulate_local_grads(
                local_gf, params, mb, accum)

            # nonfinite guard needs GLOBAL consensus: a nan can appear on
            # one device's shard only, and a divergent skip decision would
            # desync mu across devices.
            ok = jax.lax.pmin(
                precision.finite_all(grads).astype(jnp.int32),
                comm_compress.AXIS).astype(bool)
            updates, new_ob = self.optimizer.update(grads, ob, params)
            keep = lambda new, old: jax.tree.map(
                lambda n, o: jnp.where(ok, n, o), new, old)
            new_params = keep(jax.tree.map(
                lambda p, u: p + u.astype(p.dtype), params, updates), params)
            new_ob = ob._replace(
                step=jnp.where(ok, new_ob.step, ob.step),
                mu=keep(new_ob.mu, ob.mu),
                nu=keep(new_ob.nu, ob.nu),
                err=keep(new_ob.err, ob.err))
            # approximation: sqrt(E_dev ||g_local||^2) — the exact global
            # grad never exists on any device in this mode
            gnorm = jnp.sqrt(jax.lax.pmean(
                jnp.square(global_norm(grads)), comm_compress.AXIS))
            new_opt = new_ob._replace(err=jax.tree.map(
                lambda e: e[None], new_ob.err))
            return new_params, new_opt, \
                jax.lax.pmean(loss, comm_compress.AXIS), gnorm, ok

        repl = lambda tree: jax.tree.map(lambda _: P(), tree)
        err_spec = jax.tree.map(lambda _: P("data"), state.params)
        # opt_state specs: everything P() except the err stack
        opt_specs = type(state.opt_state)(
            step=P(),
            mu=repl(state.opt_state.mu),
            nu=repl(state.opt_state.nu),
            err=err_spec)
        new_params, new_opt, loss, gnorm, ok = jax.shard_map(
            f, mesh=ms.mesh,
            in_specs=(repl(state.params), opt_specs,
                      jax.tree.map(lambda _: P("data"), batch)),
            out_specs=(repl(state.params), opt_specs, P(), P(), P()),
            check_vma=False)(state.params, state.opt_state, batch)
        new_state = TrainState(
            step=state.step + jnp.where(ok, 1, 0).astype(jnp.int32),
            params=new_params, opt_state=new_opt,
            scaler=state.scaler)
        metrics = {"loss": loss, "grad_norm": gnorm,
                   "overflow": (~ok).astype(jnp.int32),
                   "lr": self.lr_schedule(state.step + 1),
                   "loss_scale": state.scaler.scale}
        return new_state, metrics

    def _eval_step(self, state: TrainState, batch):
        from deepspeed_tpu import topology as _topo

        _topo.set_current_mesh(self.mesh,
                               zero_stage=self.config.zero.stage)
        try:
            params = state.params
            if self.grad_comm_mode == "qwz":
                # flat [world, chunk] master → model leaves (GSPMD inserts
                # the gather; eval is exact, not int8-quantized)
                params = self._qwz_unflatten(
                    params.reshape(-1),
                    precision.master_dtype(self.config.precision))
            loss, aux = self._loss_for(params, batch)
            return loss if aux is None else (loss, aux)
        finally:
            set_current_mesh(self.mesh)         # as the train step's

    # ----------------------------------------------------------- public API
    @property
    def skipped_steps(self) -> int:
        """Overflow-skipped step count (ref: engine.skipped_steps)."""
        return self._skipped_base + int(self._skipped_acc)

    @skipped_steps.setter
    def skipped_steps(self, value: int) -> None:
        self._skipped_base = int(value)
        self._skipped_acc = jnp.zeros([], jnp.int32)

    def _post_step(self, metrics) -> None:
        """Per-step bookkeeping shared by train_batch and step().

        Kept sync-free unless a monitor backend is enabled: the overflow
        counter accumulates on-device, and the throughput timer (which
        drains the dispatch queue) only runs when someone will read it.
        """
        self.global_steps += 1
        self._last_metrics = metrics
        self._skipped_acc = self._skipped_acc + metrics["overflow"]
        if self.progressive_layer_drop is not None:
            self.progressive_layer_drop.update_state(self.global_steps)
        if self.monitor.enabled and (
                self.global_steps % max(self.config.steps_per_print, 1) == 0):
            self.monitor.write_scalars(
                {"Train/loss": float(metrics["loss"]),
                 "Train/lr": float(metrics["lr"]),
                 "Train/grad_norm": float(metrics["grad_norm"]),
                 "Train/samples_per_sec": self.tput_timer.samples_per_sec},
                self.global_steps)
            self.monitor.flush()
        if self.registry.enabled:
            self._c_train_steps.inc()
            if self._comm_wire is not None:
                # analytic per-step wire bytes (tree size is static —
                # this is what the schedule moved, not an estimate)
                self._c_comm_int8.inc(
                    self._comm_wire["hier_int8_payload_bytes"])
                self._c_comm_f32.inc(
                    self._comm_wire["hier_f32_payload_bytes"])
            reads = self.monitor.enabled or self._tel_exporter is not None
            if reads and (self.global_steps
                          % max(self.config.steps_per_print, 1) == 0):
                # gauge refresh syncs (float() on device scalars) — only
                # on the cadence a sink actually reads
                self._refresh_gauges(metrics)
            if self._tel_exporter is not None:
                self._tel_exporter.maybe_export(self.global_steps)

    def _comm_tick(self, _now) -> None:
        """Exporter tick hook: keep the comm gauges current for history
        sampling (they are step-invariant — configuration truth — so a
        plain re-set is exact)."""
        self._g_comm_ratio.set(self._comm_wire["ratio_vs_f32"])
        self._g_comm_overlap.set(self._comm_overlap)

    def comm_info(self) -> Optional[dict]:
        """The `comm` observability block: resolved hierarchy + analytic
        per-step wire accounting (statusz-shaped; None when no
        compressed-comm mode is active)."""
        if self._comm_wire is None:
            return None
        h = self._comm_hier
        return {
            "mode": self.grad_comm_mode,
            "hierarchy": {"world": h.world, "intra": h.intra,
                          "inter": h.inter, "flat": h.flat},
            "overlap_efficiency_bound": self._comm_overlap,
            "wire": dict(self._comm_wire),
        }

    def _refresh_gauges(self, metrics) -> None:
        self._g_loss.set(float(metrics["loss"]))
        if "lr" in metrics:
            self._g_lr.set(float(metrics["lr"]))
        if metrics.get("grad_norm") is not None:
            self._g_grad_norm.set(float(metrics["grad_norm"]))
        self._g_sps.set(self.tput_timer.samples_per_sec)
        self._g_mfu.set(self.tput_timer.mfu)
        from deepspeed_tpu import comm as _comm

        self.registry.fan_in_comms(_comm.comms_logger())

    def telemetry_snapshot(self) -> dict:
        """On-demand registry snapshot with the synced gauges refreshed
        from the last step's metrics (this is the one deliberate sync
        point for callers that run without any monitor backend)."""
        if self.registry.enabled and self._last_metrics:
            self._refresh_gauges(self._last_metrics)
        return self.registry.snapshot()

    def _align_batch(self, batch):
        """Place every batch leaf for the step: arrays with a batch dim
        get the data-sharded placement, scalars ride along replicated.
        Committed device arrays (e.g. hybrid-engine rollouts) are
        re-placed only when their sharding disagrees; host arrays are
        transferred exactly as jit's in_shardings used to."""
        import numpy as np

        repl = self.mesh.replicated()

        def fix(x):
            if isinstance(x, jax.Array):
                want = self._batch_sharding if x.ndim >= 1 else repl
                if not x.sharding.is_equivalent_to(want, x.ndim):
                    return jax.device_put(x, want)
                return x
            a = np.asarray(x)  # one sharded host→device transfer, direct
            return jax.device_put(
                a, self._batch_sharding if a.ndim >= 1 else repl)

        return jax.tree.map(fix, batch)

    def random_ltd_scheduler(self, seq_len: int):
        """Build the configured random-LTD scheduler for a model's
        sequence length (ref: the reference engine's random_ltd hooks —
        the kept-token count needs the model seq_len, which only the
        model knows, hence a factory rather than an attribute)."""
        if self.config.random_ltd is None:
            raise ValueError(
                "no data_efficiency.data_routing.random_ltd block in the "
                "config")
        from deepspeed_tpu.random_ltd import RandomLTDScheduler

        return RandomLTDScheduler(self.config.random_ltd, seq_len)

    def curriculum_difficulty(self) -> Optional[int]:
        """Current curriculum difficulty (ref: engine.curriculum_scheduler
        .get_difficulty), or None when no curriculum is configured."""
        if self.curriculum_scheduler is None:
            return None
        return self.curriculum_scheduler.get_difficulty(self.global_steps)

    def _apply_curriculum(self, batch):
        from deepspeed_tpu.data.curriculum import apply_seqlen_curriculum

        return apply_seqlen_curriculum(batch, self.curriculum_scheduler,
                                       self.global_steps)

    def train_batch(self, batch) -> jnp.ndarray:
        """Run one full optimizer step on a global batch; returns the loss.

        (ref: PipelineEngine.train_batch — one call per global step.)
        """
        batch = self._apply_curriculum(batch)
        timed = self.monitor.enabled or self._tel_sync
        with self._sp_align:
            batch = self._align_batch(batch)
        if timed:
            self.tput_timer.start()
        # train_step_seconds: the dispatch's host wall, or, when timed,
        # through the ThroughputTimer's sync; no forced sync otherwise.
        # The first dispatch makes the step program ready: the build's
        # (dstpu/build_step), not a step of the histogram (and no local
        # for it: this frame's size is part of set-up, PERF.md 6, PR 37)
        if self._build is not None:
            self._build.attach()
        with (self._sp_step if self._build is None
              else self._sp_build_step):
            self.state, metrics = self._step_fn(self.state, batch)
            if timed:
                self.tput_timer.stop()
        if self._build is not None:
            self._build.built()
            self._build = None
        self._post_step(metrics)
        return metrics["loss"]

    def eval_batch(self, batch):
        return self._eval_fn(self.state, self._align_batch(batch))

    def lower_step(self, batch):
        """Lower the train step against the ALIGNED batch — the program
        train_batch actually runs.  HLO/memory inspection must go through
        here: the step jit leaves batch shardings unspecified (placement
        happens in _align_batch), so lowering a raw host batch would
        inspect a differently-sharded program.  Curriculum truncation
        applies for the same reason — same shapes as the real step."""
        batch = self._apply_curriculum(batch)
        return self._step_fn.lower(self.state, self._align_batch(batch))

    # torch-idiom compatibility shims (ref: engine.__call__/backward/step)
    def __call__(self, batch):
        # State is committed immediately — the step donates the old buffers,
        # so holding them in a "pending" slot would leave self.state pointing
        # at deleted arrays.  backward()/step() validate call order only.
        batch = self._apply_curriculum(batch)
        new_state, metrics = self._step_fn(self.state, self._align_batch(batch))
        self.state = new_state
        self._pending = metrics
        self._last_metrics = metrics
        return metrics["loss"]

    def forward(self, batch):
        return self(batch)

    def backward(self, loss):
        """No-op: backward is fused into the compiled step."""
        if self._pending is None:
            raise RuntimeError("backward() without a preceding engine(batch) call")
        return loss

    def step(self):
        """Complete the step started by ``engine(batch)`` (bookkeeping only)."""
        if self._pending is None:
            raise RuntimeError("step() without a preceding engine(batch) call")
        metrics, self._pending = self._pending, None
        self._post_step(metrics)

    # ------------------------------------------------------------ inspection
    @property
    def metrics(self):
        return self._last_metrics

    def get_lr(self):
        return [float(self.lr_schedule(self.state.step))]

    def get_global_grad_norm(self) -> float:
        m = self._last_metrics.get("grad_norm")
        return float(m) if m is not None else 0.0

    def comms_digest(self, batch, link_gbps: float = 45.0):
        """Per-collective count/bytes digest of the compiled train step
        (ref: deepspeed/comm/comm.py comms_logger — theirs counts NCCL
        calls at runtime; ours reads the collectives GSPMD actually
        emitted from the compiled HLO).  Writes to the monitor when one
        is enabled; returns the digest dict."""
        from deepspeed_tpu.comm.digest import digest_compiled, log_digest

        compiled = self.lower_step(batch).compile()
        d = digest_compiled(compiled, link_gbps)
        if self.monitor.enabled:
            log_digest(self.monitor, d, self.global_steps)
        return d

    @property
    def train_batch_size(self):
        return self.config.train_batch_size

    @property
    def gradient_accumulation_steps(self):
        return self.config.gradient_accumulation_steps

    def module_params(self):
        """Replicated (gathered) view of params for export."""
        if self.grad_comm_mode == "qwz":
            mdt = precision.master_dtype(self.config.precision)
            repl = self.mesh.replicated()
            out_sh = jax.tree_util.tree_unflatten(
                self._qwz_treedef, [repl] * len(self._qwz_shapes))
            return jax.jit(
                lambda flat: self._qwz_unflatten(flat.reshape(-1), mdt),
                out_shardings=out_sh)(self.state.params)
        return zero.unshard_params(self.state.params, self.mesh)

    # ---------------------------------------------------------- checkpointing
    def save_checkpoint(self, save_dir: str, tag: Optional[str] = None,
                        client_state: Optional[dict] = None,
                        async_save: bool = False):
        from deepspeed_tpu.checkpoint import save_checkpoint as _save

        return _save(self, save_dir, tag=tag, client_state=client_state,
                     async_save=async_save)

    def wait_for_checkpoint(self):
        from deepspeed_tpu.checkpoint import wait_for_checkpoint as _wait

        return _wait(self)

    def load_checkpoint(self, load_dir: str, tag: Optional[str] = None):
        from deepspeed_tpu.checkpoint import load_checkpoint as _load

        return _load(self, load_dir, tag=tag)


def initialize(args=None, *, loss_fn: Optional[Callable] = None,
               params: Any = None,
               config: Any = None, mesh: Optional[MeshSpec] = None,
               optimizer: Optional[Optimizer] = None,
               lr_scheduler=None, param_specs: "zero.SpecTree" = None,
               training_data=None, has_aux: bool = False,
               dist_init_required: Optional[bool] = None):
    """ref: deepspeed.initialize — returns (engine, optimizer, dataloader,
    lr_scheduler).  ``config`` may be a dict, a path, or a Config."""
    from deepspeed_tpu import comm

    if dist_init_required is None or dist_init_required:
        comm.init_distributed()
    if config is None and args is not None:
        config = getattr(args, "deepspeed_config", None)
    if isinstance(config, str):
        config = Config.from_file(config)
    elif isinstance(config, dict):
        config = Config.from_dict(config)
    elif config is None:
        config = Config()

    # ZeRO-Infinity routing: an NVMe optimizer tier (or a cpu tier on a
    # backend without pinned_host memory) needs host-scheduled state
    # streaming — IO cannot live inside the jitted step (ref:
    # deepspeed/runtime/swap_tensor/partitioned_optimizer_swapper.py).
    # ZeRO-Infinity PARAMETER offload: a scheduled offload_param tier
    # streams bf16 params layer-by-layer around fwd+bwd, so the compute
    # copy never fully resides in HBM (ref: partitioned_param_swapper.py).
    # Requires the layered-model factoring (params = LayeredModel).
    from deepspeed_tpu.param_stream import LayeredModel, ParamStreamEngine

    poff = config.zero.offload_param or {}
    poff_dev = poff.get("device", "none")
    if isinstance(params, LayeredModel) or (
            poff_dev == "nvme" or (poff_dev == "cpu"
                                   and poff.get("scheduled"))):
        if not isinstance(params, LayeredModel):
            raise ValueError(
                "scheduled parameter offload streams per-layer programs "
                "and needs the model factored for it: pass params="
                "<model>.layered_model(cfg, params) (llama provides one); "
                "plain pytrees only support the memory-kind offload path")
        if optimizer is not None or has_aux:
            raise ValueError(
                "the param-stream engine drives its own CPU-Adam; "
                "configure the optimizer via the config block and drop "
                "has_aux (LayeredModel.block_has_aux covers it)")
        engine = ParamStreamEngine(params, config, mesh=mesh,
                                   lr_scheduler=lr_scheduler,
                                   param_specs=param_specs)
        return _finish_initialize(engine, config, training_data)

    if loss_fn is None or params is None:
        raise ValueError("initialize() needs loss_fn and params (a "
                         "LayeredModel params carries its own loss)")

    off = config.zero.offload_optimizer or {}
    off_dev = off.get("device", "none")
    if off_dev == "nvme" or (off_dev == "cpu" and off.get("scheduled")):
        from deepspeed_tpu.infinity import InfinityEngine

        if optimizer is not None or has_aux:
            raise ValueError(
                "the ZeRO-Infinity scheduled-offload engine drives its own "
                "Adam update; pass the optimizer via the config block and "
                "drop has_aux (param_specs ARE supported: TP shardings on "
                "the compute params compose with the [dp, chunk] state)")
        if config.curriculum is not None and config.curriculum.enabled:
            raise ValueError(
                "curriculum_learning does not compose with the scheduled "
                "ZeRO-Infinity engine yet — drop one of the two (the "
                "TrainingEngine honors curriculum; Infinity ignores it, "
                "which would be a silent no-op)")
        if _is_init_thunk(params):
            # zero.Init thunk: the Infinity engine keeps bf16 compute params
            # resident in HBM regardless, so materialize the thunk eagerly
            params = params()
        engine = InfinityEngine(loss_fn, params, config, mesh=mesh,
                                lr_scheduler=lr_scheduler,
                                param_specs=param_specs)
    else:
        engine = TrainingEngine(loss_fn, params, config, mesh=mesh,
                                optimizer=optimizer, lr_scheduler=lr_scheduler,
                                param_specs=param_specs, has_aux=has_aux)
    return _finish_initialize(engine, config, training_data)


def _finish_initialize(engine, config, training_data):
    """Shared initialize() tail: build the dataloader (every engine path
    must honor ``training_data``) and return the 4-tuple."""
    dataloader = None
    if training_data is not None:
        from deepspeed_tpu.data.loader import DataLoader

        dataloader = DataLoader(training_data,
                                batch_size=config.train_batch_size,
                                seed=config.seed)
    return engine, engine.optimizer, dataloader, engine.lr_schedule
