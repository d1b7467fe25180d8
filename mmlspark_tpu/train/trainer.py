"""SPMD data-parallel trainer with step-level checkpointing and
fault-tolerant execution.

Reference training path (CNTKLearner.fit, cntk-train/src/main/scala/
CNTKLearner.scala:52-162): export the whole dataset to a text file, generate
BrainScript, launch ``mpiexec -n <#GPUs> cntk ... parallelTrain=true`` and let
CNTK's MPI ring do data-parallel SGD; no mid-training resume (SURVEY.md §5).

TPU-native replacement, per BASELINE.json's north star:
- no file round-trip: host batches feed device HBM directly
  (:mod:`mmlspark_tpu.data.feed`),
- the MPI ring becomes ONE jit-compiled train step over a named mesh —
  batches sharded on the ``data`` axis, params replicated; XLA compiles the
  gradient reduction to an all-reduce over ICI (the `lax.psum` the north star
  names appears implicitly from the sharding annotations; scaling-book
  recipe),
- ``TrainConfig`` replaces generated BrainScript (BrainscriptBuilder.scala),
- step-level checkpoint/resume via an atomically-committed manifest over
  orbax (:mod:`mmlspark_tpu.train.resilience`) — a capability upgrade the
  survey flags as required (§5 checkpoint/resume).

Resilience (docs/TRAINING.md): the trainer fires the four ``train.*``
fault hook sites (core/faults.py) and survives each of them —
transient step/data faults are retried with capped deterministic
backoff, ``RESOURCE_EXHAUSTED`` walks a power-of-two
gradient-accumulation ladder instead of dying, non-finite or exploding
gradients are quarantined IN-GRAPH (params, optimizer state, and model
stats all revert to the pre-step values, so a skipped step is a pure
data advance), and a ``kill`` is the crash the bit-exact-resume drill
restores from: the atomic checkpoint carries params, optimizer state,
the anomaly streak, the step count, and the loss history, and the
seed-deterministic data order makes the resumed run bit-identical to
an uninterrupted one. Every hook is one ``is not None`` check when
``faults`` is None (the ``train_resilience`` bench group pins the
overhead to noise).
"""

from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Any, Callable, Iterator

import numpy as np

from mmlspark_tpu.core.exceptions import FriendlyError, ParamError
from mmlspark_tpu.core.faults import (
    EngineKilled,
    FaultInjector,
    is_resource_exhausted,
    is_transient,
)
from mmlspark_tpu.core.integrity import CheckpointCorruption
from mmlspark_tpu.core.logging_utils import get_logger
from mmlspark_tpu.core.telemetry import (
    FlightRecorder,
    MetricRegistry,
    SpanTracer,
)
from mmlspark_tpu.models.graph import NamedGraph
from mmlspark_tpu.parallel.mesh import DATA_AXIS, batch_spec, make_mesh, replicated_spec

_log = get_logger("train")

SOFTMAX_XENT = "softmax_xent"
SIGMOID_XENT = "sigmoid_xent"
MSE = "mse"


@dataclass(frozen=True)
class TrainConfig:
    """Everything the generated BrainScript used to say
    (BrainscriptBuilder.toOverrideConfig, BrainscriptBuilder.scala:103-115),
    as a typed config object."""

    epochs: int = 1
    batch_size: int = 128  # global batch; split over the data axis
    learning_rate: float = 1e-3
    optimizer: str = "adam"  # adam | adamw | sgd | momentum
    loss: str = SOFTMAX_XENT
    weight_decay: float = 0.0
    momentum: float = 0.9
    lr_schedule: str = "constant"  # constant | cosine
    warmup_steps: int = 0
    seed: int = 0
    log_every: int = 50
    shuffle: bool = True
    # chain K optimizer steps inside ONE compiled call (lax.scan over K
    # stacked batches): cuts per-step host dispatch to 1/K. Semantics are
    # exact: every batch is still one optimizer step; epoch tails that
    # don't fill a chunk run through the single-step program. Ignored
    # (forced 1) under tensor-parallel param_rules.
    steps_per_dispatch: int = 1
    # rematerialize the forward pass in the backward (jax.checkpoint):
    # trades ~33% more FLOPs for not keeping activations in HBM — the
    # standard lever when activation memory, not compute, caps batch size
    remat: bool = False
    # accumulate gradients over K equal micro-batches inside one
    # optimizer step (lax.scan over the split batch): the effective
    # batch stays batch_size while activation memory drops to 1/K — the
    # complementary lever to remat when memory caps the batch. Exact for
    # mean losses over equal micro-batches (grads are averaged before
    # the single optimizer update). NOT bit-equivalent for MoE models:
    # sown auxiliary losses (load-balance) are computed per micro-batch
    # and averaged, so expert routing balances within each micro-batch
    # rather than across the full batch — a slightly different (still
    # unbiased-in-spirit, standard-practice) estimator than accum=1.
    grad_accum: int = 1
    # weight on sown auxiliary losses (e.g. MoE load-balance, models/moe.py)
    moe_aux_weight: float = 1e-2
    # mesh: axis name -> size; None = all devices on the data axis
    mesh_axes: dict | None = None
    # tensor-parallel param sharding rules: ordered (regex, spec_tuple)
    # pairs (see parallel/sharding.py, e.g. TRANSFORMER_TP_RULES); None =
    # fully replicated params (the reference's only strategy)
    param_rules: Any = None
    # step-level checkpointing (train/resilience.py atomic store)
    checkpoint_dir: str | None = None
    checkpoint_every: int = 0  # steps; 0 = only at end
    max_checkpoints: int = 3
    resume: bool = True
    # -- resilience knobs (docs/TRAINING.md) ----------------------------
    # abort (FriendlyError + flight-recorder dump) after this many
    # CONSECUTIVE quarantined steps; the host check syncs at log_every
    # cadence, so the abort lags the Nth bad step by < log_every steps.
    # 0 disables the abort (quarantine still skips each bad step).
    anomaly_limit: int = 5
    # grad-norm explosion threshold for the quarantine predicate; 0 =
    # only non-finite loss/grad_norm count as anomalies
    max_grad_norm: float = 0.0
    # capped retries for transient train.step/train.data/train.restore
    # faults, with deterministic linear backoff retry_backoff_s*attempt
    retry_limit: int = 3
    retry_backoff_s: float = 0.0
    # integrity audit cadence (docs/TRAINING.md "Integrity audits"):
    # every N steps the compiled step folds a bitcast-uint32 checksum
    # of params+optimizer state into its donated carry, and the host
    # cross-checks every data-parallel replica's copy for bit-identity
    # (silent-data-corruption detection; a mismatch quarantines the
    # divergent replica and runs the deterministic-replay adjudicator).
    # 0 disables the audit — the step program is then byte-identical
    # to an integrity-unaware build, so default runs pay nothing.
    audit_every: int = 0


def _make_optimizer(cfg: TrainConfig, total_steps: int):
    import optax

    if cfg.lr_schedule == "cosine":
        lr: Any = optax.warmup_cosine_decay_schedule(
            0.0, cfg.learning_rate, max(cfg.warmup_steps, 1),
            max(total_steps, 2),
        )
    elif cfg.warmup_steps > 0:
        lr = optax.linear_schedule(0.0, cfg.learning_rate, cfg.warmup_steps)
    else:
        lr = cfg.learning_rate
    if cfg.optimizer == "adam":
        return optax.adam(lr)
    if cfg.optimizer == "adamw":
        return optax.adamw(lr, weight_decay=cfg.weight_decay)
    if cfg.optimizer == "sgd":
        return optax.sgd(lr)
    if cfg.optimizer == "momentum":
        return optax.sgd(lr, momentum=cfg.momentum)
    raise ParamError(f"unknown optimizer '{cfg.optimizer}'")


def masked_loss(kind: str, logits, labels, mask):
    """Mask-weighted mean loss. The mask marks real (non-padding) rows so
    fixed-shape batches never skew gradients."""
    import jax.numpy as jnp
    import optax

    w = mask.astype(jnp.float32)
    if logits.ndim == 3:
        # sequence model: (B, T, C) -> per-token loss, row mask broadcast
        # over T (padding rows weight 0 for every token)
        w = w[:, None] * jnp.ones(logits.shape[:2], jnp.float32)
    if kind == SOFTMAX_XENT:
        per = optax.softmax_cross_entropy_with_integer_labels(
            logits, labels.astype(jnp.int32)
        )
    elif kind == SIGMOID_XENT:
        per = optax.sigmoid_binary_cross_entropy(
            logits[..., 0], labels.astype(jnp.float32)
        )
    elif kind == MSE:
        pred = logits[..., 0] if logits.ndim > w.ndim else logits
        per = jnp.square(pred - labels.astype(jnp.float32))
    else:
        raise ParamError(f"unknown loss '{kind}'")
    return jnp.sum(per * w) / jnp.maximum(jnp.sum(w), 1.0)


def _sown_aux_loss(variables: dict):
    """Sum of every value sown into a block's ``losses`` collection (MoE
    load-balance terms, models/moe.py); 0.0 when none exist."""
    import jax

    total = 0.0
    for block_vars in variables.values():
        if isinstance(block_vars, dict) and "losses" in block_vars:
            for leaf in jax.tree_util.tree_leaves(block_vars["losses"]):
                total = total + leaf.sum()
    return total


def _split_variables(variables: dict) -> tuple[dict, dict]:
    """Per-block variables -> (trainable params tree, static/stats tree).

    Sown per-call ``losses`` are consumed by :func:`_sown_aux_loss` before
    this split and must NOT ride along in ``rest``: they would change the
    carried tree structure after step 0 (forcing a recompile and breaking
    checkpoint restore against the init-derived target).
    """
    params = {b: v.get("params", {}) for b, v in variables.items()}
    rest = {
        b: {k: c for k, c in v.items() if k not in ("params", "losses")}
        for b, v in variables.items()
    }
    return params, rest


def _merge_variables(params: dict, rest: dict) -> dict:
    return {b: {"params": params[b], **rest.get(b, {})} for b in params}


class SPMDTrainer:
    """Train a NamedGraph with one compiled sharded step.

    ``train(x, y)`` owns the epoch loop; the per-step program is compiled
    once (fixed shapes from the feed layer) and reused — the analog of the
    reference's single external training run, minus the process boundary.

    ``faults`` (a :class:`~mmlspark_tpu.core.faults.FaultInjector`, or
    None) drives the ``train.*`` drill sites; ``recorder`` collects the
    step/checkpoint/restore/anomaly/retry/degraded event timeline
    (docs/TRAINING.md "Failure semantics").
    """

    def __init__(self, graph: NamedGraph, config: TrainConfig,
                 telemetry: MetricRegistry | None = None,
                 recorder: FlightRecorder | None = None,
                 faults: FaultInjector | None = None):
        self.graph = graph
        self.config = config
        self.history: list[dict] = []
        #: loss-curve entries carried over from a restored checkpoint's
        #: manifest — kept SEPARATE from :attr:`history` (this run's own
        #: curve) so step arithmetic over ``history`` is resume-invariant;
        #: ``restored_history + history`` is the full curve and is what
        #: the next checkpoint persists
        self.restored_history: list[dict] = []
        #: per-trainer metric registry (core/telemetry): step-time,
        #: tokens/sec, loss, and grad-norm histograms, recorded at
        #: ``log_every`` cadence — ``telemetry.to_dict()`` is the flat
        #: percentile view (docs/OBSERVABILITY.md)
        self.telemetry = telemetry if telemetry is not None \
            else MetricRegistry()
        #: flight recorder (core/telemetry): the trainer's event
        #: timeline, dumped automatically when a FriendlyError (e.g.
        #: the anomaly abort) escapes ``train()``
        self.recorder = recorder if recorder is not None \
            else FlightRecorder()
        #: host intervals of the loop (``train.step`` and the four
        #: inside it), into the same recorder and the profiler's trace
        self._tracer = SpanTracer(self.recorder)
        self._faults = faults
        self._step = 0  # current global step, for the fault listener's tick
        #: (jitted single-step program, abstract signature of its first
        #: dispatch) — what :meth:`step_cost` lowers on demand
        self._step_program: tuple | None = None
        if faults is not None and faults.listener is None:
            # injected faults land in the same metrics + event timeline
            # as their consequences (retries, quarantines, degradation)
            def _on_fault(kind: str, site: str) -> None:
                self.telemetry.counter("train.faults_injected_total").inc()
                self.recorder.record(
                    "fault_injected", tick=self._step, kind=kind, site=site,
                )
            faults.listener = _on_fault
        #: deterministic-replay adjudications, newest last: each entry
        #: names the audit step, the verdict ("transient_sdc" when the
        #: replay reproduces the majority/device checksum — the flip
        #: was isolated corruption of a copy at rest — or
        #: "software_nondeterminism" when the recomputation itself
        #: disagrees), and the three checksums compared
        self.replay_verdicts: list[dict] = []
        # pre-created so the exported schema is stable whether or not a
        # fault ever fires (tools/check_metrics_schema.py --train)
        for name in ("train.retries_total", "train.anomalies_skipped",
                     "train.checkpoints", "train.checkpoint_failures",
                     "train.faults_injected_total",
                     "train.integrity.audits",
                     "train.integrity.checksum_failures",
                     "train.integrity.sdc_suspected",
                     "train.integrity.replay_transient_sdc",
                     "train.integrity.replay_software_nondeterminism"):
            self.telemetry.counter(name)
        self.telemetry.gauge("train.grad_accum").set(
            max(int(config.grad_accum), 1)
        )

    def step_cost(self):
        """Analytic cost (:class:`~mmlspark_tpu.core.perf.ProgramCost`:
        FLOPs, bytes, Pallas kernel count) of the single-step program
        this trainer last ran, lowered on demand from the abstract
        signature of its first dispatch — tracing only, no compile, no
        device work."""
        from mmlspark_tpu.core.perf import analyze_jit_cost

        if self._step_program is None:
            raise FriendlyError(
                "step_cost() needs a step to have run: call train() first"
            )
        jitted, args = self._step_program
        return analyze_jit_cost(jitted, *args)

    # -- checkpointing ------------------------------------------------------

    def _ckpt_store(self):
        cfg = self.config
        if not cfg.checkpoint_dir:
            return None
        from mmlspark_tpu.train.resilience import AtomicCheckpointStore

        def pre_commit(step: int) -> None:
            # the torn-write drill window: fires between the payload
            # write and the manifest commit (docs/TRAINING.md
            # "Checkpoint atomicity")
            if self._faults is not None:
                self._faults.fire("train.checkpoint", tick=step)

        def post_hash(step: int, payload_dir: str) -> None:
            # the silent-corruption drill window: a corrupt fault here
            # bit-flips the payload AFTER its sha256 was taken, so the
            # manifest commits a hash the bytes no longer match —
            # detected only when a verified restore looks
            if self._faults is None:
                return
            seed = self._faults.corrupt_spec("train.checkpoint",
                                             tick=step)
            if seed is not None:
                from mmlspark_tpu.core import integrity

                integrity.flip_bit_in_dir(payload_dir, seed)

        return AtomicCheckpointStore(
            cfg.checkpoint_dir, max_to_keep=cfg.max_checkpoints,
            pre_commit=pre_commit, post_hash=post_hash,
        )

    # -- fault hooks --------------------------------------------------------

    def _fire_hook(self, site: str, tick: int) -> None:
        """Fire one fault hook site; transient faults are absorbed by up
        to ``retry_limit`` retries with deterministic linear backoff.
        Fired BEFORE the guarded work (dispatch, batch use, restore
        read) so a raised fault never consumes donated buffers and a
        retry is always safe. OOM/kill escape to the caller's policy."""
        if self._faults is None:
            return
        cfg = self.config
        attempt = 0
        while True:
            try:
                self._faults.fire(site, tick=tick)
                return
            except Exception as e:
                if is_transient(e) and attempt < cfg.retry_limit:
                    attempt += 1
                    self.telemetry.counter("train.retries_total").inc()
                    self.recorder.record(
                        "retry", tick=tick, site=site, attempt=attempt,
                    )
                    if cfg.retry_backoff_s:
                        time.sleep(cfg.retry_backoff_s * attempt)
                    continue
                raise

    # -- main loop ----------------------------------------------------------

    def train(
        self,
        x: np.ndarray,
        y: np.ndarray,
        init_variables: dict | None = None,
        eval_fn: Callable[[dict], dict] | None = None,
    ) -> dict:
        """Run the configured number of epochs over (x, y); returns trained
        variables. Resumes from the newest committed checkpoint when
        configured. A :class:`FriendlyError` escaping this call (the
        anomaly-streak abort, an exhausted accumulation ladder) dumps
        the flight recorder first — the black-box contract."""
        with self.recorder.dump_on_friendly_error():
            return self._train_impl(x, y, init_variables, eval_fn)

    def _train_impl(self, x, y, init_variables, eval_fn) -> dict:
        import jax
        import jax.numpy as jnp
        import optax

        from mmlspark_tpu.train.resilience import next_accum_rung

        cfg = self.config
        n = len(x)
        if n == 0:
            raise FriendlyError("empty training set")
        mesh = make_mesh(cfg.mesh_axes)
        n_data = mesh.shape.get(DATA_AXIS, 1)
        batch = cfg.batch_size
        if batch % n_data:
            batch += n_data - batch % n_data
        steps_per_epoch = -(-n // batch)  # ceil: batch_iterator pads the tail
        total_steps = steps_per_epoch * cfg.epochs
        tx = _make_optimizer(cfg, total_steps)

        rng = jax.random.PRNGKey(cfg.seed)
        if init_variables is None:
            sample = jnp.asarray(x[:1])
            init_variables = self.graph.init(rng, sample)
        params, rest = _split_variables(init_variables)
        opt_state = tx.init(params)
        step0 = 0
        # in-graph anomaly carries: consecutive-bad-step streak and the
        # cumulative quarantined-step count, donated alongside the state
        # so the quarantine costs no extra host syncs
        streak0 = np.zeros((), np.int32)
        anoms0 = np.zeros((), np.int32)
        seen_anoms = 0  # last total synced into the per-run counter

        store = self._ckpt_store()
        restored = None
        meta: dict = {}
        latest: int | None = None
        if store is not None and cfg.resume and store.latest_step() is not None:
            latest = store.latest_step()
            target = {
                "params": jax.device_get(params),
                "rest": jax.device_get(rest),
                "opt_state": jax.device_get(opt_state),
                "anomaly": {"streak": streak0, "total": anoms0},
            }
            while latest is not None:
                # train.restore drill site: transient -> retried read,
                # kill -> the restore itself crashed (escape)
                self._fire_hook("train.restore", latest)
                try:
                    restored, meta, latest = store.restore(target)
                    break
                except CheckpointCorruption as e:
                    # verified restore (docs/TRAINING.md "Integrity
                    # audits"): the store already quarantined the
                    # corrupt step, so the retry lands on the previous
                    # committed checkpoint — or a cold start when no
                    # intact checkpoint remains
                    self.telemetry.counter(
                        "train.integrity.checksum_failures"
                    ).inc()
                    self.recorder.record(
                        "integrity.checksum_failure", tick=e.step,
                        surface="checkpoint", expected=e.expected,
                        actual=e.actual,
                    )
                    _log.warning("%s", e)
                    latest = store.latest_step()
        if restored is not None:
            params = restored["params"]
            rest = restored["rest"]
            opt_state = restored["opt_state"]
            streak0 = restored["anomaly"]["streak"]
            anoms0 = restored["anomaly"]["total"]
            seen_anoms = int(anoms0)
            self.restored_history = list(meta.get("history", []))
            spe = meta.get("steps_per_epoch")
            if spe is not None and int(spe) != steps_per_epoch:
                raise FriendlyError(
                    f"checkpoint at {cfg.checkpoint_dir!r} was taken with "
                    f"steps_per_epoch={spe} but this run computes "
                    f"{steps_per_epoch} (batch {batch} over {n_data} data "
                    "shards): elastic resume needs a batch_size divisible "
                    "by both the old and new data-axis widths so the "
                    "deterministic data order is unchanged"
                )
            step0 = latest + 1
            self.recorder.record("restore", tick=latest,
                                 anomalies_total=seen_anoms)
            _log.info("resumed from checkpoint step %d", latest)

        data_sh = batch_spec(mesh)
        rep_sh = replicated_spec(mesh)
        # attention runs its kernels per shard of the step's mesh (duck-
        # typed graphs that own their mesh, e.g. the pipelined family,
        # have no with_mesh and need none)
        graph = self.graph
        if mesh.size > 1 and hasattr(graph, "with_mesh"):
            graph = graph.with_mesh(mesh)
        loss_kind = cfg.loss

        aux_w = cfg.moe_aux_weight
        max_gnorm = float(cfg.max_grad_norm)
        # forward the padding mask only to graphs that accept it (user
        # duck-typed graphs may predate the mask kwarg)
        import inspect

        takes_mask = "mask" in inspect.signature(graph.apply).parameters

        def fwd(variables, bx, bmask):
            mask_kw = {"mask": bmask} if takes_mask else {}
            return graph.apply(variables, bx, train=True, **mask_kw)

        if cfg.remat:
            # recompute the forward during the backward instead of holding
            # activations in HBM
            fwd = jax.checkpoint(fwd)

        accum = max(int(cfg.grad_accum), 1)
        if accum > 1 and batch % (accum * n_data):
            raise FriendlyError(
                f"grad_accum={accum} needs the (data-axis rounded) batch "
                f"size {batch} divisible by accum x data-axis size "
                f"({accum * n_data})"
            )

        audit_every = max(int(cfg.audit_every), 0)
        audit = audit_every > 0

        def make_step_fn(accum: int, audit: bool = False):
            """One optimizer step at the given accumulation rung, with the
            in-graph anomaly quarantine fused at the end.

            With ``audit`` the signature grows a donated uint32 checksum
            carry plus a ``do_audit`` flag: on audit steps a bitcast
            fold of the post-step params + optimizer state
            (:func:`~mmlspark_tpu.core.integrity.tree_checksum`)
            replaces the carry under ``lax.cond`` — non-audit steps
            skip the fold entirely, and the host only reads the carry
            at audit cadence, so the audit adds no per-step host
            sync (docs/TRAINING.md "Integrity audits")."""

            def step_fn(params, rest, opt_state, streak, anoms,
                        bx, by, bmask):
                def loss_fn(p, r, mx, my, mm):
                    variables = _merge_variables(p, r)
                    out, updated = fwd(variables, mx, mm)
                    loss = masked_loss(loss_kind, out, my, mm)
                    loss = loss + aux_w * _sown_aux_loss(updated)
                    _, new_rest = _split_variables(updated)
                    return loss, new_rest

                if accum == 1:
                    (loss, new_rest), grads = jax.value_and_grad(
                        loss_fn, has_aux=True
                    )(params, rest, bx, by, bmask)
                else:
                    # micro-batch scan: grads sum in f32 param space, ONE
                    # optimizer update at the end — activations for only one
                    # micro-batch are ever live. Two exactness details:
                    # - STRIDED split (row i -> micro i % accum): each
                    #   device's contiguous data-axis shard feeds every
                    #   micro-batch locally (a contiguous split would move
                    #   whole micro-batches across the mesh every step), and
                    #   the padded tail spreads over micro-batches;
                    # - WEIGHTED accumulation: each micro contributes its
                    #   masked loss SUM and mask count, normalized once at
                    #   the end — uniform averaging of per-micro means would
                    #   shrink the step by up to accum when padding
                    #   concentrates in some micro-batches (masked_loss
                    #   normalizes by its own batch's count).
                    split = lambda t: t.reshape(  # noqa: E731
                        t.shape[0] // accum, accum, *t.shape[1:]
                    ).swapaxes(0, 1)

                    def sum_loss_fn(p, r, mx, my, mm):
                        l, r2 = loss_fn(p, r, mx, my, mm)
                        cnt = jnp.sum(mm.astype(jnp.float32))
                        return l * jnp.maximum(cnt, 1.0), (r2, cnt)

                    def body(carry, xs):
                        gsum, lsum, csum, r = carry
                        (ls, (r, cnt)), g = jax.value_and_grad(
                            sum_loss_fn, has_aux=True
                        )(params, r, *xs)
                        gsum = jax.tree_util.tree_map(jnp.add, gsum, g)
                        return (gsum, lsum + ls, csum + cnt, r), None

                    zero = jax.tree_util.tree_map(jnp.zeros_like, params)
                    f0 = jnp.asarray(0.0, jnp.float32)
                    (gsum, lsum, csum, new_rest), _ = jax.lax.scan(
                        body,
                        (zero, f0, f0, rest),
                        (split(bx), split(by), split(bmask)),
                    )
                    denom = jnp.maximum(csum, 1.0)
                    grads = jax.tree_util.tree_map(
                        lambda t: t / denom, gsum
                    )
                    loss = lsum / denom
                # global grad norm BEFORE the optimizer transform: the
                # scale-blowup/vanishing signal the telemetry histograms
                # track — one extra scalar through the existing fetch
                gnorm = optax.global_norm(grads)
                updates, new_opt = tx.update(grads, opt_state, params)
                new_params = optax.apply_updates(params, updates)
                # grad-anomaly quarantine (docs/TRAINING.md): a non-finite
                # loss/grad-norm (or an explosion past max_grad_norm)
                # reverts params, optimizer state, AND model stats to the
                # pre-step values — the update is skipped entirely and
                # the optimizer's own step count does not advance. On a
                # healthy step every select picks the new leaf, so the
                # quarantine is bit-invisible to anomaly-free runs.
                bad = jnp.logical_or(
                    jnp.logical_not(jnp.isfinite(loss)),
                    jnp.logical_not(jnp.isfinite(gnorm)),
                )
                if max_gnorm > 0.0:
                    bad = jnp.logical_or(bad, gnorm > max_gnorm)

                def keep(new, old):
                    return jax.tree_util.tree_map(
                        lambda nl, ol: jnp.where(bad, ol, nl), new, old
                    )

                new_params = keep(new_params, params)
                new_opt = keep(new_opt, opt_state)
                new_rest = keep(new_rest, rest)
                streak = jnp.where(bad, streak + 1,
                                   jnp.zeros_like(streak))
                anoms = anoms + bad.astype(anoms.dtype)
                return (new_params, new_rest, new_opt, streak, anoms,
                        loss, gnorm)

            if not audit:
                return step_fn

            from mmlspark_tpu.core.integrity import tree_checksum

            def step_audit(params, rest, opt_state, streak, anoms, chk,
                           bx, by, bmask, do_audit):
                (new_params, new_rest, new_opt, streak, anoms, loss,
                 gnorm) = step_fn(params, rest, opt_state, streak,
                                  anoms, bx, by, bmask)
                chk2 = jax.lax.cond(
                    do_audit,
                    lambda p, o: tree_checksum((p, o)),
                    lambda p, o: chk,
                    new_params, new_opt,
                )
                return (new_params, new_rest, new_opt, streak, anoms,
                        chk2, loss, gnorm)

            return step_audit

        k_steps = max(int(cfg.steps_per_dispatch), 1)
        if cfg.param_rules:
            k_steps = 1  # TP branch compiles without explicit shardings

        if cfg.param_rules:
            # tensor parallelism: shard params per rule set; optimizer
            # state inherits each param's sharding (GSPMD propagates
            # through tx.init), and the train step is compiled without
            # explicit shardings — committed inputs drive GSPMD, which
            # inserts the ICI collectives.
            from mmlspark_tpu.parallel.sharding import build_param_shardings

            param_sh = build_param_shardings(params, mesh, cfg.param_rules)
            params = jax.device_put(params, param_sh)
            opt_template = jax.jit(tx.init)(params)
            mesh_devs = set(mesh.devices.flat)

            def _opt_sharding(leaf):
                # leaves tx.init derived from params keep the param
                # sharding; fresh scalars (step counts) land on one device
                # and must be re-replicated over the mesh
                if set(leaf.sharding.device_set) == mesh_devs:
                    return leaf.sharding
                return rep_sh

            opt_state = jax.tree_util.tree_map(
                lambda t, v: jax.device_put(
                    jnp.asarray(v), _opt_sharding(t)
                ),
                opt_template,
                opt_state,
            )
            rest = jax.device_put(rest, rep_sh)
        else:
            params = jax.device_put(params, rep_sh)
            rest = jax.device_put(rest, rep_sh)
            opt_state = jax.device_put(opt_state, rep_sh)
        streak_dev = jax.device_put(jnp.asarray(streak0, jnp.int32), rep_sh)
        anoms_dev = jax.device_put(jnp.asarray(anoms0, jnp.int32), rep_sh)

        from jax.sharding import NamedSharding, PartitionSpec as P

        # batch dim is axis 1 of the (K, batch, ...) stacks
        chunk_sh = NamedSharding(mesh, P(None, DATA_AXIS))

        def build_programs(accum: int):
            """Compile the step (and K-step chunk) programs at one
            accumulation rung. Called once up front and once per rung
            the OOM degrade ladder descends to — one compile per rung,
            the same honesty as serve's decode-block ladder. With
            audits on, every program carries the extra donated uint32
            checksum slot; with audits off the signatures are exactly
            the pre-integrity ones (bit-identical programs)."""
            step_fn = make_step_fn(accum, audit)
            n_carry = 6 if audit else 5
            n_out = 8 if audit else 7
            if cfg.param_rules:
                jitted = jax.jit(
                    step_fn, donate_argnums=tuple(range(n_carry))
                )
                return jitted, None
            in_sh = (rep_sh,) * n_carry + (data_sh,) * 3
            if audit:
                in_sh = in_sh + (rep_sh,)
            jitted = jax.jit(
                step_fn,
                in_shardings=in_sh,
                out_shardings=(rep_sh,) * n_out,
                donate_argnums=tuple(range(n_carry)),
            )
            chunk_jitted = None
            if k_steps > 1:
                inner = make_step_fn(accum, False)

                def scan_chunk(params, rest, opt_state, streak, anoms,
                               bxs, bys, bms):
                    def body(carry, xs):
                        p, r, o, s, a = carry
                        p, r, o, s, a, loss, gnorm = inner(
                            p, r, o, s, a, *xs
                        )
                        return (p, r, o, s, a), (loss, gnorm)

                    return jax.lax.scan(
                        body, (params, rest, opt_state, streak, anoms),
                        (bxs, bys, bms),
                    )

                if audit:
                    from mmlspark_tpu.core.integrity import tree_checksum

                    def chunk_fn(params, rest, opt_state, streak, anoms,
                                 chk, bxs, bys, bms, do_audit):
                        (params, rest, opt_state, streak, anoms), \
                            (losses, gnorms) = scan_chunk(
                                params, rest, opt_state, streak, anoms,
                                bxs, bys, bms,
                            )
                        # audit cadence coarsens to the dispatch-chunk
                        # boundary, the same honesty as the log cadence
                        chk2 = jax.lax.cond(
                            do_audit,
                            lambda p, o: tree_checksum((p, o)),
                            lambda p, o: chk,
                            params, opt_state,
                        )
                        return (params, rest, opt_state, streak, anoms,
                                chk2, losses[-1], gnorms[-1])
                else:
                    def chunk_fn(params, rest, opt_state, streak, anoms,
                                 bxs, bys, bms):
                        (params, rest, opt_state, streak, anoms), \
                            (losses, gnorms) = scan_chunk(
                                params, rest, opt_state, streak, anoms,
                                bxs, bys, bms,
                            )
                        return (params, rest, opt_state, streak, anoms,
                                losses[-1], gnorms[-1])

                chunk_in = (rep_sh,) * n_carry + (chunk_sh,) * 3
                if audit:
                    chunk_in = chunk_in + (rep_sh,)
                chunk_jitted = jax.jit(
                    chunk_fn,
                    in_shardings=chunk_in,
                    out_shardings=(rep_sh,) * n_out,
                    donate_argnums=tuple(range(n_carry)),
                )
            return jitted, chunk_jitted

        jitted, chunk_jitted = build_programs(accum)

        # -- integrity audit state (docs/TRAINING.md "Integrity audits") --
        # chk_dev is the donated uint32 carry; the flags are device
        # residents so flipping audit on/off per dispatch never re-lands
        # a host scalar (which would retrace nothing but still costs a
        # transfer per step)
        from mmlspark_tpu.core import integrity as _integrity

        if audit:
            chk_dev = jax.device_put(jnp.zeros((), jnp.uint32), rep_sh)
            flag_on = jax.device_put(jnp.asarray(True), rep_sh)
            flag_off = jax.device_put(jnp.asarray(False), rep_sh)
        else:
            chk_dev = flag_on = flag_off = None
        audit_base: dict | None = None
        audit_buf: list[tuple] = []

        def refresh_base() -> None:
            """Host twin of the current state — the deterministic-replay
            adjudicator's known-good starting point — plus a cleared
            dispatch buffer. Refreshed after every audit (clean or not)
            so replay windows never exceed one audit interval."""
            nonlocal audit_base
            audit_base = {
                "params": jax.device_get(params),
                "rest": jax.device_get(rest),
                "opt_state": jax.device_get(opt_state),
                "streak": jax.device_get(streak_dev),
                "anoms": jax.device_get(anoms_dev),
            }
            audit_buf.clear()

        def replay_from_base():
            """Re-execute every dispatch since the last clean audit from
            the host-twin base through the SAME compiled programs;
            returns the replayed carries + a host fold of the replayed
            params/opt-state, or ``None`` when there is nothing to
            replay (no base yet, or a TP run where per-replica replay
            has no meaning)."""
            if audit_base is None or not audit_buf or cfg.param_rules:
                return None
            p = jax.device_put(audit_base["params"], rep_sh)
            r = jax.device_put(audit_base["rest"], rep_sh)
            o = jax.device_put(audit_base["opt_state"], rep_sh)
            s = jax.device_put(jnp.asarray(audit_base["streak"]), rep_sh)
            a = jax.device_put(jnp.asarray(audit_base["anoms"]), rep_sh)
            c = jax.device_put(jnp.zeros((), jnp.uint32), rep_sh)
            for entry in list(audit_buf):
                if entry[0] == "chunk":
                    stacks = tuple(
                        jax.device_put(jnp.asarray(t), chunk_sh)
                        for t in entry[1]
                    )
                    p, r, o, s, a, c, _, _ = chunk_jitted(
                        p, r, o, s, a, c, *stacks, flag_off
                    )
                else:
                    bx, by, bm = (
                        jax.device_put(jnp.asarray(t), data_sh)
                        for t in entry[1:]
                    )
                    p, r, o, s, a, c, _, _ = jitted(
                        p, r, o, s, a, c, bx, by, bm, flag_off
                    )
            fold = _integrity.tree_checksum_host(
                (jax.device_get(p), jax.device_get(o))
            )
            return p, r, o, s, a, fold

        def run_audit(at_step: int) -> None:
            """Cross-replica integrity audit: the compiled step's
            in-graph fold (``chk_dev``) is compared against a host fold
            of EVERY device's copy of params + optimizer state.
            Data-parallel replicas are bit-identical by construction
            (grads are psum'd identically everywhere), so any
            disagreement is silent data corruption or software
            nondeterminism — the replay adjudicator tells them apart by
            re-running the interval from the last known-good host twin:
            a reproducible majority means the original flip was a
            one-off (transient SDC); an unreproducible fold means the
            step program itself is nondeterministic."""
            nonlocal params, rest, opt_state, streak_dev, anoms_dev
            self.telemetry.counter("train.integrity.audits").inc()
            chk_val = int(chk_dev)
            if cfg.param_rules:
                # TP-sharded params: per-device copies are partial
                # shards with no replica redundancy to vote with; the
                # only comparable host fold is over the assembled arrays
                folds = {-1: _integrity.tree_checksum_host(
                    (jax.device_get(params), jax.device_get(opt_state))
                )}
            else:
                folds = _integrity.per_device_checksums(
                    (params, opt_state)
                )
            from collections import Counter

            counts = Counter(folds.values())
            top = max(counts.values())
            majority = min(v for v, n in counts.items() if n == top)
            divergent = sorted(d for d, v in folds.items()
                               if v != majority)
            if not divergent and majority == chk_val:
                refresh_base()
                return
            self.telemetry.counter("train.integrity.sdc_suspected").inc()
            self.recorder.record(
                "integrity.sdc_suspected", tick=at_step,
                device_checksum=chk_val, majority_checksum=majority,
                divergent_devices=[int(d) for d in divergent],
            )
            _log.warning(
                "step %d: integrity audit mismatch (in-graph fold %d, "
                "majority host fold %d, divergent device copies %s) — "
                "silent data corruption suspected",
                at_step, chk_val, majority, divergent,
            )
            if divergent and not cfg.param_rules:
                # quarantine the divergent replicas: re-replicate every
                # carry from a majority device — the same
                # revert-to-known-good move as the anomaly quarantine,
                # applied across the replica axis
                src = min(d for d, v in folds.items() if v == majority)
                p_h, r_h, o_h, s_h, a_h = _integrity.device_copy(
                    (params, rest, opt_state, streak_dev, anoms_dev),
                    src,
                )
                params = jax.device_put(p_h, rep_sh)
                rest = jax.device_put(r_h, rep_sh)
                opt_state = jax.device_put(o_h, rep_sh)
                streak_dev = jax.device_put(jnp.asarray(s_h), rep_sh)
                anoms_dev = jax.device_put(jnp.asarray(a_h), rep_sh)
                self.recorder.record(
                    "integrity.replica_quarantined", tick=at_step,
                    devices=[int(d) for d in divergent],
                    source=int(src),
                )
                _log.warning(
                    "step %d: quarantined divergent replica copies %s; "
                    "re-replicated from device %d", at_step,
                    [int(d) for d in divergent], src,
                )
            replayed = replay_from_base()
            if replayed is not None:
                p, r, o, s, a, fold = replayed
                verdict = (
                    "transient_sdc" if fold in (majority, chk_val)
                    else "software_nondeterminism"
                )
                self.telemetry.counter(
                    "train.integrity.replay_transient_sdc"
                    if verdict == "transient_sdc" else
                    "train.integrity.replay_software_nondeterminism"
                ).inc()
                entry = {
                    "step": int(at_step), "verdict": verdict,
                    "replayed_checksum": int(fold),
                    "device_checksum": int(chk_val),
                    "majority_checksum": int(majority),
                }
                self.replay_verdicts.append(entry)
                self.recorder.record(
                    "integrity.replay", tick=at_step,
                    **{k: v for k, v in entry.items() if k != "step"},
                )
                _log.warning("step %d: replay adjudication -> %s",
                             at_step, verdict)
                if verdict == "transient_sdc" and not divergent:
                    # no majority vote repaired the state (every replica
                    # copy agreed with the corrupt lineage): adopt the
                    # verified replayed state as current
                    params, rest, opt_state = p, r, o
                    streak_dev, anoms_dev = s, a
            refresh_base()

        def guarded_fire(tick: int) -> None:
            """The ``train.step`` hook + its resilience policy, fired
            BEFORE the jitted call (donated buffers survive a raised
            fault): transients are retried inside :meth:`_fire_hook`;
            RESOURCE_EXHAUSTED walks down the power-of-two accumulation
            ladder and recompiles; ``kill`` escapes — the crash drill
            the atomic checkpoint restores from."""
            nonlocal accum, jitted, chunk_jitted
            while True:
                try:
                    self._fire_hook("train.step", tick)
                    return
                except Exception as e:
                    if is_resource_exhausted(e):
                        nxt = next_accum_rung(accum, batch=batch,
                                              n_data=n_data)
                        if nxt is None:
                            raise FriendlyError(
                                f"RESOURCE_EXHAUSTED at step {tick} with "
                                f"the gradient-accumulation ladder "
                                f"exhausted (grad_accum={accum}, batch "
                                f"{batch} over {n_data} data shards) — "
                                "reduce batch_size or model size"
                            ) from e
                        accum = nxt
                        self.telemetry.gauge("train.grad_accum").set(accum)
                        self.recorder.record("degraded", tick=tick,
                                             grad_accum=accum)
                        _log.warning(
                            "step %d: RESOURCE_EXHAUSTED -> degrading to "
                            "grad_accum=%d and recompiling", tick, accum,
                        )
                        jitted, chunk_jitted = build_programs(accum)
                        continue
                    raise

        def pull_guard(b: dict, tick: int) -> dict:
            """The ``train.data`` hook: transients retried, poison
            NaN-corrupts the first float feature/label row — the
            injected stand-in for a bad gradient the quarantine must
            skip."""
            self._fire_hook("train.data", tick)
            if self._faults.poison_value("train.data", tick=tick) is None:
                return b
            b = dict(b)
            for col in ("x", "y"):
                arr = np.asarray(b[col])
                if np.issubdtype(arr.dtype, np.floating):
                    arr = np.array(arr, copy=True)
                    arr[0] = np.nan
                    b[col] = arr
                    break
            else:
                _log.warning(
                    "train.data poison skipped at step %d: no float "
                    "column to corrupt", tick,
                )
            return b

        def save_checkpoint(at_step: int) -> None:
            """Atomic checkpoint of the full resume state. Failures
            (other than the ``kill`` crash drill) are counted and
            skipped — the previous committed checkpoint stands."""
            state = {
                "params": jax.device_get(params),
                "rest": jax.device_get(rest),
                "opt_state": jax.device_get(opt_state),
                "anomaly": {
                    "streak": jax.device_get(streak_dev),
                    "total": jax.device_get(anoms_dev),
                },
            }
            meta = {
                "steps_per_epoch": steps_per_epoch,
                "history": self.restored_history + self.history,
            }
            try:
                store.save(at_step, state, meta=meta)
            except EngineKilled:
                raise  # the torn-write crash drill escapes train()
            except Exception as e:
                self.telemetry.counter("train.checkpoint_failures").inc()
                self.recorder.record("checkpoint", tick=at_step, ok=False,
                                     error=type(e).__name__)
                _log.warning("checkpoint at step %d failed (%s); previous "
                             "checkpoint stands", at_step, e)
                return
            self.telemetry.counter("train.checkpoints").inc()
            self.recorder.record("checkpoint", tick=at_step, ok=True)

        from mmlspark_tpu.data.feed import MASK_COL, batch_iterator
        from mmlspark_tpu.data.dataset import Dataset

        if audit:
            refresh_base()
        step = step0
        self._step = step
        start_epoch = step0 // steps_per_epoch
        # Mid-epoch resume: per-epoch shuffle is seed-deterministic, so
        # skipping the first (step0 % steps_per_epoch) batches reproduces the
        # exact data position the checkpoint was taken at.
        skip_in_first = step0 % steps_per_epoch
        for epoch in range(start_epoch, cfg.epochs):
            ds = Dataset({"x": x, "y": y})
            it: Iterator = batch_iterator(
                ds,
                ["x", "y"],
                batch,
                shuffle_seed=(cfg.seed + epoch) if cfg.shuffle else None,
            )
            if epoch == start_epoch and skip_in_first:
                import itertools

                it = itertools.islice(it, skip_in_first, None)
            def grouped(batches):
                buf: list = []
                for b in batches:
                    buf.append(b)
                    if len(buf) == k_steps:
                        yield buf
                        buf = []
                if buf:
                    yield buf  # epoch tail; runs through the 1-step path

            log_every = max(cfg.log_every, 1)
            # telemetry's tokens/sec figure: rows x sequence length for
            # token-sequence inputs (2-D integer batches), plain rows
            # otherwise — the throughput unit scaling work cares about
            tokens_per_step = batch * (
                x.shape[1] if np.ndim(x) == 2 else 1
            )
            groups = grouped(it)
            while True:
                # one group of the loop is one ``train.step``, and the
                # pull of the group lies inside it: no host time of the
                # loop falls between one step's region and the next
                first = step
                step_region = self._tracer.step_region("train.step",
                                                       tick=first)
                with step_region:
                    with self._tracer.region("train.feed",
                                             tick=first) as feed:
                        group = next(groups, None)
                        if group is None:
                            # the epoch's end: nothing was fed, no step
                            feed.drop()
                            step_region.drop()
                            break
                        self._step = step
                        audit_due = False
                        if self._faults is not None:
                            group = [pull_guard(b, step + i)
                                     for i, b in enumerate(group)]
                        chunked = k_steps > 1 and len(group) == k_steps
                        if chunked:
                            fed = [tuple(
                                jax.device_put(
                                    jnp.stack([jnp.asarray(b[c])
                                               for b in group]),
                                    chunk_sh,
                                )
                                for c in ("x", "y", MASK_COL)
                            )]
                            if audit:
                                audit_buf.append(("chunk", tuple(
                                    np.stack([np.asarray(b[c])
                                              for b in group])
                                    for c in ("x", "y", MASK_COL)
                                )))
                        else:
                            fed = [tuple(
                                jax.device_put(jnp.asarray(b[c]), data_sh)
                                for c in ("x", "y", MASK_COL)
                            ) for b in group]
                            if audit:
                                audit_buf.extend((
                                    "single", np.asarray(b["x"]),
                                    np.asarray(b["y"]),
                                    np.asarray(b[MASK_COL]),
                                ) for b in group)
                    with self._tracer.region("train.dispatch", tick=first):
                        if chunked:
                            guarded_fire(step)
                            stacks = fed[0]
                            if audit:
                                due = any(
                                    (s + 1) % audit_every == 0
                                    for s in range(step, step + len(group))
                                )
                                (params, rest, opt_state, streak_dev,
                                 anoms_dev, chk_dev, loss,
                                 gnorm) = chunk_jitted(
                                    params, rest, opt_state, streak_dev,
                                    anoms_dev, chk_dev, *stacks,
                                    flag_on if due else flag_off,
                                )
                                audit_due = audit_due or due
                            else:
                                (params, rest, opt_state, streak_dev,
                                 anoms_dev, loss, gnorm) = chunk_jitted(
                                    params, rest, opt_state, streak_dev,
                                    anoms_dev, *stacks,
                                )
                            if self._faults is not None:
                                cseed = self._faults.corrupt_spec(
                                    "train.step", tick=step
                                )
                                if cseed is not None \
                                        and not cfg.param_rules:
                                    params, _ = _integrity.corrupt_replica(
                                        params, cseed
                                    )
                        else:
                            for i, (bx, by, bm) in enumerate(fed):
                                guarded_fire(step + i)
                                if audit:
                                    due = (step + i + 1) % audit_every == 0
                                    (params, rest, opt_state, streak_dev,
                                     anoms_dev, chk_dev, loss,
                                     gnorm) = jitted(
                                        params, rest, opt_state,
                                        streak_dev, anoms_dev, chk_dev,
                                        bx, by, bm,
                                        flag_on if due else flag_off,
                                    )
                                    audit_due = audit_due or due
                                else:
                                    step_args = (params, rest, opt_state,
                                                 streak_dev, anoms_dev,
                                                 bx, by, bm)
                                    if self._step_program is None:
                                        # shapes only: the arrays are
                                        # donated
                                        self._step_program = (
                                            jitted,
                                            jax.tree_util.tree_map(
                                                lambda a:
                                                jax.ShapeDtypeStruct(
                                                    a.shape, a.dtype),
                                                step_args,
                                            ),
                                        )
                                    (params, rest, opt_state, streak_dev,
                                     anoms_dev, loss,
                                     gnorm) = jitted(*step_args)
                                if self._faults is not None:
                                    # the train.step silent-corruption
                                    # drill: a seeded bit-flip lands in
                                    # ONE device's copy of one param
                                    # leaf AFTER the dispatch, so the
                                    # in-graph fold precedes the flip
                                    # and the next audit's host folds
                                    # see it
                                    cseed = self._faults.corrupt_spec(
                                        "train.step", tick=step + i
                                    )
                                    if cseed is not None \
                                            and not cfg.param_rules:
                                        params, _ = \
                                            _integrity.corrupt_replica(
                                                params, cseed
                                            )
                        n_done = len(group)
                    # log once if any step in [step, step+n) hits the
                    # cadence; the fetched loss is the group's LAST
                    # step's, so label it with that step (chunking
                    # coarsens cadence, never lies)
                    next_log = step + (-step) % log_every
                    step += n_done
                    self._step = step
                    if next_log < step:
                        # the log cadence's ONE sync: async dispatch
                        # means the host-side fetch of ``loss`` is what
                        # synchronizes the clock, and the anomaly
                        # carries ride it (the quarantine itself is
                        # in-graph, so the N-consecutive abort lags the
                        # Nth bad step by < log_every steps)
                        with self._tracer.region("train.sync",
                                                 tick=first) as sync:
                            loss_val = float(loss)
                            gnorm_val = float(gnorm)
                            streak_val = int(streak_dev)
                            anoms_val = int(anoms_dev)
                        with self._tracer.region("train.log", tick=first):
                            # the group's dispatch+device wall,
                            # amortized per step: from the step
                            # region's start to the sync's end
                            step_s = max(
                                (sync.t1 - step_region.t0) / n_done, 1e-9
                            )
                            tel = self.telemetry
                            tel.histogram("train.step_ms").record(
                                step_s * 1e3
                            )
                            tel.histogram("train.tokens_per_sec").record(
                                tokens_per_step / step_s
                            )
                            # a quarantined step's loss/gnorm is
                            # non-finite by definition — keep it out of
                            # the log-bucketed histograms (history and
                            # the anomaly counters carry the honest
                            # record)
                            if np.isfinite(loss_val):
                                tel.histogram("train.loss").record(
                                    loss_val
                                )
                            if np.isfinite(gnorm_val):
                                tel.histogram("train.grad_norm").record(
                                    gnorm_val
                                )
                            self.history.append(
                                {"step": step - 1, "epoch": epoch,
                                 "loss": loss_val, "grad_norm": gnorm_val}
                            )
                            self.recorder.record(
                                "step", tick=step - 1, epoch=epoch,
                                loss=loss_val, grad_norm=gnorm_val,
                            )
                            _log.info(
                                "step %d epoch %d loss %.5f grad_norm "
                                "%.4f step_ms %.1f", step - 1, epoch,
                                loss_val, gnorm_val, step_s * 1e3,
                            )
                            self._check_anomalies(streak_val, anoms_val,
                                                  seen_anoms, step - 1)
                            seen_anoms = max(seen_anoms, anoms_val)
                    if audit and audit_due:
                        # the interval's ONE audit host sync: read the
                        # in-graph fold and every replica's copy,
                        # adjudicate (runs BEFORE the checkpoint save so
                        # a detected corruption never gets committed to
                        # disk)
                        run_audit(step - 1)
                    if (
                        store is not None
                        and cfg.checkpoint_every
                        # any step of the finished group on the save
                        # cadence triggers a save of the current
                        # (group-end) state — with chunked dispatch the
                        # exact cadence step has no materialized state
                        # of its own
                        and any(
                            s % cfg.checkpoint_every == 0
                            for s in range(step - n_done, step)
                        )
                    ):
                        # gate BEFORE fetching: save_checkpoint
                        # device_gets the whole (possibly TP-sharded)
                        # state, which would stall async dispatch on
                        # every non-checkpoint step
                        save_checkpoint(step - 1)
            if eval_fn is not None:
                variables = _merge_variables(
                    jax.device_get(params), jax.device_get(rest)
                )
                metrics = eval_fn(variables)
                self.history.append({"step": step, "epoch": epoch, **metrics})

        # end-of-run anomaly sweep: catches a terminal bad streak that
        # never crossed a log-cadence sync point
        anoms_val = int(anoms_dev)
        self._check_anomalies(int(streak_dev), anoms_val, seen_anoms,
                              step - 1)
        seen_anoms = max(seen_anoms, anoms_val)
        if store is not None and store.latest_step() != step - 1:
            save_checkpoint(step - 1)
        final_loss = next(
            (h["loss"] for h in reversed(self.history) if "loss" in h), None
        )
        _log.info("training done: %d steps, final logged loss %s", step,
                  final_loss)
        return _merge_variables(jax.device_get(params), jax.device_get(rest))

    def _check_anomalies(self, streak_val: int, anoms_val: int,
                         seen_anoms: int, at_step: int) -> None:
        """Account for the in-graph anomaly carries as the host read
        them: count newly skipped steps and abort on a streak past the
        limit."""
        cfg = self.config
        if anoms_val > seen_anoms:
            self.telemetry.counter("train.anomalies_skipped").inc(
                anoms_val - seen_anoms
            )
            self.recorder.record(
                "anomaly", tick=at_step, streak=streak_val,
                skipped_total=anoms_val,
            )
            _log.warning(
                "step %d: %d anomalous gradient step(s) quarantined "
                "(streak %d) — params/optimizer not advanced",
                at_step, anoms_val - seen_anoms, streak_val,
            )
        if cfg.anomaly_limit and streak_val >= cfg.anomaly_limit:
            raise FriendlyError(
                f"{streak_val} consecutive anomalous gradient steps "
                f"(non-finite or exploding grad_norm) at step {at_step}; "
                f"aborting after anomaly_limit={cfg.anomaly_limit}. The "
                "quarantine kept params and optimizer state at their "
                "last healthy values — inspect the dumped flight "
                "recorder and the train.data pipeline"
            )
