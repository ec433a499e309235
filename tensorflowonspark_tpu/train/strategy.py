"""Training strategies: the MultiWorkerMirroredStrategy / NCCL replacement.

The reference delegated distributed training to TF strategies chosen by user
code (`MultiWorkerMirroredStrategy` in every TF2 example, e.g.
/root/reference/examples/mnist/keras/mnist_spark.py:11;
`ParameterServerStrategy` for async, mnist_spark_streaming.py:84-89). Here the
strategy is a thin object that owns a mesh and compiles the user's loss into a
sharded train step: batches shard over the data axes, params replicate (pure
DP) or shard along ``fsdp`` (ZeRO-3), and XLA derives the gradient all-reduce /
reduce-scatter over ICI from the shardings — there is no collective to call by
hand and no PS; sync DP over ICI serves both of the reference's modes
(SURVEY.md §2.6).
"""

import collections
import statistics
import time

import jax
import jax.numpy as jnp

from tensorflowonspark_tpu import obs
from tensorflowonspark_tpu.parallel import (
    batch_sharding,
    build_mesh,
    fsdp_param_specs,
    overlay_fsdp_specs,
    replicated,
    shard_batch,
)


class TrainState:
    """Minimal train-state pytree: step / params / opt_state / model_state.

    ``model_state`` carries non-trained variable collections (e.g. BatchNorm
    ``batch_stats`` — note that under pjit the batch-mean/var are computed over
    the *global* sharded batch, so cross-replica "sync BN" is automatic, unlike
    the reference's per-replica BN under MultiWorkerMirroredStrategy).

    Registered as a pytree so it flows through jit/grad; deliberately not
    carrying apply_fn/tx (functions don't belong in a sharded, checkpointable
    pytree — orbax saves exactly this tuple).
    """

    def __init__(self, step, params, opt_state, model_state=None):
        self.step = step
        self.params = params
        self.opt_state = opt_state
        self.model_state = {} if model_state is None else model_state

    def replace(self, **kw):
        return TrainState(
            kw.get("step", self.step),
            kw.get("params", self.params),
            kw.get("opt_state", self.opt_state),
            kw.get("model_state", self.model_state),
        )

    def tree_flatten(self):
        return (self.step, self.params, self.opt_state, self.model_state), None

    @classmethod
    def tree_unflatten(cls, _aux, children):
        return cls(*children)


jax.tree_util.register_pytree_node(
    TrainState, TrainState.tree_flatten, TrainState.tree_unflatten
)

#: how many dispatch intervals a step callable remembers, and how far over
#: their median one has to be to count as a stall
STALL_WINDOW = 32
STALL_FACTOR = 2.0


class StallMeter:
    """The last ``STALL_WINDOW`` device-paced intervals between dispatches of
    one step callable. Under a loop that keeps a bounded number of steps in
    flight the host dispatches at the device's pace, so an interval far over
    the median is a gap between steps, read without a fence."""

    def __init__(self):
        self._recent = collections.deque(maxlen=STALL_WINDOW)

    def note(self, interval):
        """Remember ``interval``; returns how far it is over the median of
        those before it when it is over ``STALL_FACTOR`` times that median,
        else 0."""
        excess = 0.0
        if self._recent:
            median = statistics.median(self._recent)
            if interval > STALL_FACTOR * median:
                excess = interval - median
        self._recent.append(interval)
        return excess


class TrainStep:
    """What :meth:`SyncDataParallel.compile_train_step` returns: the jitted
    step behind the ``step_dispatch`` span. ``lower``, ``trace`` and every
    other attribute are the jitted function's own.

    Each call is one ``tos.step_dispatch`` step annotation in a profiler
    trace and is counted (``train_steps_dispatched_total``,
    ``train_step_dispatch_seconds_total``). The first call's share of those
    seconds, which holds the step's tracing, lowering and compilation or
    cache load, is kept apart (``train_step_first_call_seconds``; the compile
    listener of ``util.place_compile_cache`` books its stages,
    ``train_step_*_seconds``). The interval since the call
    before goes to a :class:`StallMeter` only when both calls found the step
    before them still running: the device's queue was never empty, so the
    loop came back at the device's pace, and an interval the meter finds long
    means the device was late (``train_step_stalls_total``,
    ``train_step_stall_seconds_total``). Any other interval is the host's —
    the first call's compilation, a fence, a slow input, the short one after
    any of them — and is neither remembered nor booked, so the median holds
    device-paced intervals alone. (A loop that bounds nothing itself paces
    with the device only once JAX's own queue is full: until then its short
    intervals fill the history.) With collection off (``TOS_OBS=0``) a call
    is the jitted function's.

    What a model counted inside a step leaves it in the step's metrics
    under ``counter/<name>`` and ``gauge/<name>`` (``make_loss_fn`` carries
    out what the model sowed into its ``counters`` and ``gauges``
    collections) and is booked by name (``obs.book_carried``: counter
    ``<name>_total``, gauge ``<name>``, which the model registered where it
    sows) at a later call that finds that step finished: its scalars
    were sent towards the host when it was dispatched, so booking waits for
    nothing and fences nothing. :meth:`drain` books what the last steps
    carried, once the loop is over. A step that carries nothing books
    nothing.
    """

    def __init__(self, jitted):
        self._jitted = jitted
        self._meter = StallMeter()
        self._dispatched = 0
        self._last_at = None
        self._last_loss = None
        self._found_running = False
        self._unbooked = collections.deque()  # what steps not yet seen finished carried out
        # registered here, not at the first stall: a reader has to tell a
        # clean window (0) from a program that does not count (absent)
        self._steps = obs.counter(
            "train_steps_dispatched_total", help="calls of a compiled train step"
        )
        self._seconds = obs.counter(
            "train_step_dispatch_seconds_total",
            help="host seconds inside calls of a compiled train step (dispatch, "
            "and the first call's compilation or cache load: "
            "train_step_first_call_seconds is that call's share)",
        )
        self._first_call = obs.gauge(
            "train_step_first_call_seconds",
            help="host seconds inside the first call of a compiled train step: its "
            "tracing, lowering, cache key, load or compilation, and the dispatch",
        )
        self._stalls = obs.counter(
            "train_step_stalls_total",
            help="dispatch intervals that found the step before still running and "
            "were over twice the median of the last 32 such (the device was late)",
        )
        self._stall_seconds = obs.counter(
            "train_step_stall_seconds_total",
            help="seconds by which those stalls exceeded the median interval",
        )

    def __getattr__(self, name):
        return getattr(self._jitted, name)

    def __call__(self, state, batch):
        if not obs.enabled():
            return self._jitted(state, batch)
        now = time.monotonic()
        running = self._last_loss is not None and not self._last_loss.is_ready()
        if running and self._found_running:
            excess = self._meter.note(now - self._last_at)
            if excess:
                self._stalls.inc()
                self._stall_seconds.inc(excess)
        self._found_running = running
        self._last_at = now
        self._dispatched += 1
        self._steps.inc()
        with obs.span("step_dispatch", seconds_total=self._seconds, step_num=self._dispatched) as dispatch:
            out = self._jitted(state, batch)
        if self._dispatched == 1:
            self._first_call.set(dispatch.dur_s)
        self._last_loss = out[1]["loss"]
        carried = {k: v for k, v in out[1].items() if k.startswith(obs.CARRIED)}
        if carried:
            for value in carried.values():
                value.copy_to_host_async()
            self._unbooked.append(carried)
            self._book(lambda step: all(v.is_ready() for v in step.values()))
        return out

    def _book(self, finished):
        while self._unbooked and finished(self._unbooked[0]):
            obs.book_carried(self._unbooked.popleft())

    def drain(self):
        """Book what the steps still in flight carried out (waits for them)."""
        self._book(lambda step: True)


class SyncDataParallel:
    """Synchronous data parallelism (optionally fully-sharded) over a mesh.

    ``fsdp=False``: params/opt-state replicated, batch sharded over ``dp`` —
    the exact capability of the reference's collective all-reduce path.
    ``fsdp=True``: params/opt-state sharded along the ``fsdp`` axis (ZeRO-3),
    which the reference could not express at all.

    Usage inside ``main_fun(args, ctx)``::

        strategy = SyncDataParallel(ctx.mesh({"dp": -1}))
        state = strategy.create_state(model_init, optimizer, rng, sample_batch)
        step = strategy.compile_train_step(loss_fn, optimizer)
        for batch in batches:
            state, metrics = step(state, strategy.shard_batch(batch))
    """

    def __init__(self, mesh=None, fsdp=False, min_weight_size=2**14, param_spec_fn=None, tp=False):
        """``param_spec_fn(params_shape, mesh) -> PartitionSpec pytree`` lets a
        model supply its own placement rules (e.g.
        :func:`tensorflowonspark_tpu.models.transformer.param_specs` for
        tensor parallelism); default placement is replicate (pure DP) or the
        generic FSDP rules.

        ``tp`` turns on tensor parallelism over the mesh's ``tp`` axis:
        pass the model's placement rules directly (``tp=transformer.param_specs``)
        or ``tp=True`` alongside an explicit ``param_spec_fn``. Only the model
        knows which dims are column- vs row-parallel, so ``tp`` without rules
        is an error, as is a mesh without a ``tp`` axis. ``fsdp`` composes:
        the model's tp specs win where they touch, the ZeRO-3 overlay shards
        the leftovers (dp×tp and dp×fsdp×tp both come from the same rules)."""
        self.mesh = mesh if mesh is not None else build_mesh()
        self.fsdp = fsdp
        self.min_weight_size = min_weight_size
        if callable(tp):
            if param_spec_fn is not None and param_spec_fn is not tp:
                raise ValueError(
                    "pass the placement rules once: tp=<spec_fn> or "
                    "param_spec_fn=<spec_fn>, not two different functions"
                )
            param_spec_fn, tp = tp, True
        self.tp = bool(tp)
        self.param_spec_fn = param_spec_fn
        if fsdp and "fsdp" not in self.mesh.axis_names:
            raise ValueError(
                "fsdp=True requires a mesh with an 'fsdp' axis; got {}".format(
                    self.mesh.axis_names
                )
            )
        if self.tp:
            if "tp" not in self.mesh.axis_names:
                raise ValueError(
                    "tp=... requires a mesh with a 'tp' axis; got {}".format(
                        self.mesh.axis_names
                    )
                )
            if self.param_spec_fn is None:
                raise ValueError(
                    "tp=True needs the model's placement rules: pass "
                    "tp=<param_spec_fn> (e.g. models.transformer.param_specs) "
                    "or param_spec_fn= explicitly"
                )

    # -- placement ------------------------------------------------------------

    def param_shardings(self, params_shape):
        """NamedShardings for a params pytree (from shapes or real arrays).

        ``param_spec_fn`` and ``fsdp`` compose: the model's own placement
        rules run first, then the generic ZeRO-3 overlay shards any array the
        model left untouched along ``fsdp`` (params are then reduce-scattered
        / all-gathered per step by XLA from the shardings alone). The
        ``fsdp_params_sharded`` gauge reports how many param arrays actually
        ended up sharded, so a mis-sized ``min_weight_size`` (everything
        replicated) is visible in ``TFCluster.metrics()``.
        """
        from jax.sharding import NamedSharding, PartitionSpec

        if self.param_spec_fn is not None:
            specs = self.param_spec_fn(params_shape, self.mesh)
            if self.fsdp:
                specs = overlay_fsdp_specs(
                    params_shape, specs, self.mesh,
                    min_weight_size=self.min_weight_size,
                )
        elif self.fsdp:
            specs = fsdp_param_specs(
                params_shape, self.mesh, min_weight_size=self.min_weight_size
            )
        else:
            rep = PartitionSpec()
            specs = jax.tree.map(lambda _: rep, params_shape)
        if self.fsdp or self.tp:
            from tensorflowonspark_tpu import obs
            from tensorflowonspark_tpu.parallel.sharding import _spec_axes

            spec_leaves = [
                s
                for s in jax.tree.leaves(
                    specs, is_leaf=lambda n: isinstance(n, PartitionSpec)
                )
                if isinstance(s, PartitionSpec)
            ]
            if self.fsdp:
                obs.gauge(
                    "fsdp_params_sharded",
                    help="param arrays sharded along the fsdp axis (ZeRO-3)",
                ).set(sum(1 for s in spec_leaves if "fsdp" in _spec_axes(s)))
            if self.tp:
                obs.gauge(
                    "tp_params_sharded",
                    help="param arrays sharded along the tp axis (tensor parallelism)",
                ).set(sum(1 for s in spec_leaves if "tp" in _spec_axes(s)))
        return jax.tree.map(lambda s: NamedSharding(self.mesh, s), specs)

    def shard_batch(self, batch):
        return shard_batch(batch, self.mesh)

    # -- state ----------------------------------------------------------------

    @staticmethod
    def _split_variables(variables):
        """flax ``init`` returns {'params': ..., 'batch_stats': ..., ...};
        split into (params, model_state). A bare pytree is all params."""
        if isinstance(variables, dict) and "params" in variables:
            params = variables["params"]
            model_state = {k: v for k, v in variables.items() if k != "params"}
            return params, model_state
        return variables, {}

    def create_state(self, init_fn, optimizer, *init_args):
        """Build a sharded TrainState without ever materializing an unsharded
        copy: params/opt-state are initialized *inside* jit with the target
        shardings as out_shardings, so each device only ever allocates its
        shard (critical for FSDP models larger than one host's memory).

        ``init_fn(*init_args)`` returns either a bare params pytree or a flax
        variables dict (``{'params': ..., 'batch_stats': ...}``).
        """

        def _init():
            params, model_state = self._split_variables(init_fn(*init_args))
            return TrainState(
                jnp.zeros((), jnp.int32), params, optimizer.init(params), model_state
            )

        state_shape = jax.eval_shape(_init)
        shardings = TrainState(
            replicated(self.mesh),
            self.param_shardings(state_shape.params),
            self._opt_shardings(state_shape),
            jax.tree.map(lambda _: replicated(self.mesh), state_shape.model_state),
        )
        return jax.jit(_init, out_shardings=shardings)()

    def _opt_shardings(self, state_shape):
        """Opt-state shardings, matched *structurally*: optax states embed
        whole param-shaped subtrees (Adam's mu/nu, momentum's trace), so any
        opt-state subtree whose treedef and leaf shapes mirror the params gets
        the params' sharding tree; everything else (counts, scalars)
        replicates. A by-shape lookup would misplace moments when two
        same-shaped params carry different PartitionSpecs; still, leaves in
        subtrees that do NOT fully mirror the params (e.g. optax.masked
        moments with MaskedNode sentinels) fall back to a per-leaf
        shape-match so large moment arrays keep their sharding instead of
        blowing up replicated."""
        param_shardings = self.param_shardings(state_shape.params)
        params_def = jax.tree.structure(state_shape.params)
        param_leaves = jax.tree.leaves(state_shape.params)
        rep = replicated(self.mesh)
        by_shape = {}
        for p_leaf, s in zip(param_leaves, jax.tree.leaves(param_shardings)):
            by_shape.setdefault((p_leaf.shape, p_leaf.dtype), s)

        def _is_param_like(sub):
            if jax.tree.structure(sub) != params_def:
                return False
            leaves = jax.tree.leaves(sub)
            return all(
                getattr(a, "shape", None) == b.shape
                and getattr(a, "dtype", None) == b.dtype
                for a, b in zip(leaves, param_leaves)
            )

        def _assign(sub):
            if _is_param_like(sub):
                return param_shardings
            return by_shape.get(
                (getattr(sub, "shape", None), getattr(sub, "dtype", None)), rep
            )

        return jax.tree.map(_assign, state_shape.opt_state, is_leaf=_is_param_like)

    # -- compiled steps --------------------------------------------------------

    def compile_train_step(self, loss_fn, optimizer, has_aux=False, mutable=False, donate=True):
        """Compile a loss into a sharded ``step(state, batch) -> (state, metrics)``.

        * ``mutable=False``: ``loss_fn(params, batch) -> loss`` or
          ``(loss, aux_metrics)`` with ``has_aux=True``.
        * ``mutable=True`` (models with batch_stats etc.):
          ``loss_fn(params, model_state, batch) -> (loss, (new_model_state,
          aux_metrics))`` — ``has_aux`` is implied.

        The gradient all-reduce (pure DP) or reduce-scatter+all-gather (FSDP)
        is inserted by XLA from the shardings — the moral equivalent of the
        reference's `all_reduce_alg`/NCCL configuration, with zero user code.

        A ``loss_fn`` that declares a ``step`` keyword receives the current
        ``state.step`` — the supported way to vary per-step randomness
        (dropout rngs) without smuggling counters through the batch.

        The callable is a :class:`TrainStep`: the jitted function behind the
        ``step_dispatch`` span and its counters.
        """
        return TrainStep(self._jit_train_step(loss_fn, optimizer, has_aux, mutable, donate))

    def _jit_train_step(self, loss_fn, optimizer, has_aux, mutable, donate):
        """The bare jitted step. Its two halves carry ``jax.named_scope``s,
        so every operation's ``op_name`` in the compiled program and in a
        profiler trace says its phase: under ``tos.optimizer`` the optimizer;
        under ``tos.loss_and_grad`` the forward pass, or with
        ``transpose(jvp(`` in it the backward pass, or with
        ``rematted_computation`` a block computed again for it."""
        import inspect

        import optax

        try:
            wants_step = "step" in inspect.signature(loss_fn).parameters
        except (TypeError, ValueError):
            wants_step = False

        def tos_train_step(state, batch):
            kw = {"step": state.step} if wants_step else {}
            with jax.named_scope("tos.loss_and_grad"):
                if mutable:
                    (loss, (model_state, aux)), grads = jax.value_and_grad(
                        loss_fn, has_aux=True
                    )(state.params, state.model_state, batch, **kw)
                else:
                    out = jax.value_and_grad(loss_fn, has_aux=has_aux)(state.params, batch, **kw)
                    (loss, aux), grads = out if has_aux else ((out[0], None), out[1])
                    model_state = state.model_state
            with jax.named_scope("tos.optimizer"):
                updates, opt_state = optimizer.update(grads, state.opt_state, state.params)
                params = optax.apply_updates(state.params, updates)
            new_state = TrainState(state.step + 1, params, opt_state, model_state)
            metrics = {"loss": loss, "step": new_state.step}
            if aux:
                metrics.update(aux)
            return new_state, metrics

        return jax.jit(tos_train_step, donate_argnums=(0,) if donate else ())

    def compile_train_loop(self, loss_fn, optimizer, num_steps, has_aux=False, mutable=False, donate=True):
        """Compile ``loop(state, batches) -> (state, last_metrics)`` running
        ``num_steps`` train steps INSIDE one XLA program via ``lax.scan``.

        ``batches`` is a list/tuple of ``num_steps`` per-step batch pytrees,
        each already device-resident via :meth:`shard_batch` — place them as
        they arrive from the feed so the host→device transfers run
        asynchronously, overlapping the previous loop's compute (see
        :func:`tensorflowonspark_tpu.data.loop_prefetch`). The stack into the
        scan's ``[K, batch, ...]`` carry happens ON DEVICE (an HBM-to-HBM
        copy XLA aliases away under donation), never on the host: a host-side
        ``np.stack`` + one bulk transfer sits on the critical path and loses
        to per-step dispatch, which is why this API takes device arrays.

        One device dispatch per ``num_steps`` steps: where the per-dispatch
        host round trip is comparable to the step time it dominates, and
        scanning it away is the difference between host-bound and MXU-bound
        training (no reference analogue: TF sessions had the same per-step
        host loop this removes).

        With ``donate=True`` (default) the state is donated and the batches
        are not: a batch aliases no output (a uint8 image or an int32 token
        leaf cannot alias the param leaves), so offering it only produced
        XLA's "Some donated buffers were not usable" warning and a silent
        copy, and :func:`~tensorflowonspark_tpu.data.loop_prefetch` keeps
        the placed batches referenced while the loop runs.
        """
        step = self._jit_train_step(loss_fn, optimizer, has_aux, mutable, donate=False)

        def loop(state, batches):
            if len(batches) != num_steps:
                raise ValueError(
                    "got {} batches, loop compiled for {}".format(
                        len(batches), num_steps
                    )
                )
            stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *batches)

            def body(carry, batch):
                new_state, metrics = step(carry, batch)
                return new_state, metrics

            state, metrics = jax.lax.scan(body, state, stacked)
            # metrics of the LAST step (scan stacks them; take index -1)
            return state, jax.tree.map(lambda m: m[-1], metrics)

        return jax.jit(loop, donate_argnums=(0,) if donate else ())

    def compile_eval_step(self, metric_fn):
        """Compile ``metric_fn(params, batch) -> metrics`` for sharded eval."""
        return jax.jit(metric_fn)

    def compile_predict_step(self, apply_fn):
        """Compile ``apply_fn(params, batch) -> predictions``; outputs gather
        to fully-addressable arrays for host-side result queues."""
        return jax.jit(apply_fn, out_shardings=replicated(self.mesh))


def run_steps(step_fn, state, batches, engine=None, save_every_n=None, hooks=()):
    """Drive a compiled step over ``batches`` with per-step hooks and
    non-blocking checkpointing. Returns ``(state, last_metrics)``.

    The loop hook for the async checkpoint engine
    (:class:`tensorflowonspark_tpu.ckpt.AsyncCheckpointEngine`): every
    ``save_every_n`` steps (default: the engine's own cadence) the state is
    snapshotted to host — the only checkpoint cost the training thread ever
    pays — and committed in the background; on exit (including an exception
    unwinding through the loop) the engine is **drained** so the final
    snapshot lands before the caller tears anything down.

    Donation-safe by ordering: ``step_fn`` may donate its state argument —
    the snapshot copies the *returned* state to host buffers the engine
    owns before the next iteration donates those device arrays back into
    ``step_fn``, so the background writer never aliases live device memory.

    ``hooks`` are callables ``hook(state, global_step, metrics)`` run after
    every step (eval triggers, LR logging). The global step is tracked
    host-side from one initial ``state.step`` readback — per-step device
    syncs would serialize the dispatch pipeline this loop exists to keep
    full.
    """
    import jax

    if isinstance(state, dict):  # bare-pytree states carry step as a key
        start = state.get("step", 0)
    else:
        start = getattr(state, "step", 0)
    start_step = int(jax.device_get(start))
    cadence = save_every_n if save_every_n is not None else (
        engine.save_every_n if engine is not None else 0
    )
    from tensorflowonspark_tpu import obs

    # per-step phase spans (fetch / compute / snapshot): each lands in the
    # flight shard for the merged step timeline AND in the {phase}_seconds
    # histogram the exporter's /histograms.json summarizes as p50/p99.
    # obs.span hands out a shared no-op span when collection is disabled.
    metrics = None
    it = iter(batches)
    i = 0
    try:
        while True:
            with obs.span("step_fetch", step=start_step + i + 1):
                try:
                    batch = next(it)
                except StopIteration:
                    break
            with obs.span("step_compute", step=start_step + i + 1):
                state, metrics = step_fn(state, batch)
            global_step = start_step + i + 1
            for hook in hooks:
                hook(state, global_step, metrics)
            if engine is not None and cadence and global_step % cadence == 0:
                with obs.span("ckpt_snapshot", step=global_step):
                    engine.save(state, global_step)
            i += 1
    finally:
        if engine is not None:
            engine.drain()
    return state, metrics


def steps_per_worker(total_examples, batch_size, num_workers, safety=0.9):
    """Per-worker step budget for InputMode.SPARK feeding.

    Spark partitions are uneven, so a worker that demands exactly
    ``total/batch/workers`` steps can starve at the epoch tail and hang the
    collective. The reference buried this as example folklore — "limit
    steps to ~90% of expected to account for uneven partitions"
    (/root/reference/examples/mnist/keras/mnist_spark.py:58-64); here it is
    the documented helper.
    """
    per_worker = total_examples // (batch_size * max(num_workers, 1))
    return max(1, int(per_worker * safety))
