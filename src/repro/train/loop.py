"""Fault-tolerant distributed training loop with paper-driven recovery.

The paper shows (Fig. 7) that an impending MX divergence can be averted by
switching the precision scheme mid-training *before* the loss blows up.
This loop operationalizes that as a two-tier fault-tolerance policy:

  0. **autopilot (first line)**: with ``TrainerConfig.guard`` set, a
     `repro.guard.PrecisionController` watches in-jit risk signals
     (loss-EMA curvature, grad-norm ratio, and lax.cond-gated ζ-bound /
     LN-clamp probes — see guard/monitors.py) and escalates the precision
     scheme *before* the spike heuristic would fire; after a stability
     window it de-escalates back toward MX to recover throughput.  Every
     transition is journaled as a ``guard_transition`` event (with
     ``qcfg.describe()`` before/after) and persisted in checkpoint meta,
     so resumes adopt the autopilot state and the journaled schedule
     replays the run bitwise.  Transitions take effect at metric-drain
     boundaries (per step when ``log_every=1``);
  1. watchdog (last line): SpikeDetector on loss + gradient norm
     (App. B heuristic);
  2. on trigger: roll back to the last good checkpoint (async, versioned);
  3. apply the configured intervention (default: "bf16_activations", the
     paper's strongest immediate stabilizer) — this swaps the static
     QuantConfig, recompiling the step function, and training resumes
     from the rollback step with the identical data stream (step-indexed
     batches make the replay exact).  Without a checkpointer the
     intervention still applies (forward fix, no rollback);
  4. after ``max_recoveries`` the run *aborts* with a terminal
     ``recovery_exhausted`` event — a deterministic spike must never
     replay forever (restore -> same data -> same spike -> restore);
  5. events are recorded for the run report.

Distribution: pass ``mesh`` to run sharded.  Parameters and optimizer
state shard FSDP+TP per `parallel.sharding.param_pspecs`, batches shard
over the ("pod", "data") axes, and the jitted step carries explicit
in/out shardings so placement never depends on GSPMD guessing.  With a
"pod" axis the gradient exchange across the slow inter-pod links runs
inside a `shard_map` over "pod" and goes through `compressed_psum`
(optionally MX-compressed, `TrainerConfig.pod_compression`), surfacing
the paper's ζ-norm-style `compression_error` as a per-step metric.
``grad_accum > 1`` splits each global batch into sequential microbatches
with fp32 accumulation (same loss, k× smaller activation working set).

Node-failure recovery falls out of the same machinery: restart the binary,
`Trainer.restore()` picks the newest complete checkpoint — adopting the
checkpoint's *recorded* QuantConfig and recovery count, so a resume never
silently reverts a mid-run intervention — and the data pipeline
fast-forwards by step index (elastic across mesh shapes since checkpoints
are logically unsharded).  A step-time monitor flags straggler steps.

Host sync discipline: step metrics stay on device; the loop drains them
(one blocking transfer per window) only at ``log_every``/checkpoint
boundaries, feeding the watchdog every step of the window in order.
Checkpoints are written only after their window drains clean, so a
rollback target is never contaminated by an undetected spike.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import warnings
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core import (QuantConfig, SpikeDetector, apply_intervention,
                        fused_gemms_enabled, get_format, use_fused_gemms)
from repro.optim import AdamWConfig, adamw_init, adamw_update, warmup_cosine
from repro.runtime import (Journal, MemoryLedger, MetricsWindow, SegmentFn,
                           SegmentTracker, checkpoint_meta,
                           parse_checkpoint_meta)

__all__ = ["TrainerConfig", "Trainer", "make_train_step"]


@dataclasses.dataclass
class TrainerConfig:
    total_steps: int = 1000
    peak_lr: float = 2e-4
    init_lr: float = 2e-5
    end_lr: float = 2e-5
    warmup_frac: float = 0.05
    ckpt_every: int = 200
    ckpt_dir: Optional[str] = None
    keep_ckpts: int = 3
    # instability watchdog / recovery
    spike_factor: float = 100.0
    grad_factor: float = 50.0
    auto_intervention: Optional[str] = "bf16_activations"
    max_recoveries: int = 3
    # precision autopilot (first line of defense; repro.guard).  A policy
    # preset name ("autopilot", "aggressive", ..., or "sched:STEP=..."),
    # or a GuardPolicy instance.  None disables the controller.
    guard: Optional[Any] = None
    guard_probe_every: int = 25       # ζ/clamp probe stride (0 = off)
    # straggler monitor
    straggler_factor: float = 3.0
    log_every: int = 50
    # distribution
    grad_accum: int = 1                      # microbatches per step
    pod_compression: Optional[str] = None    # e.g. "e4m3": MX cross-pod grads


def _microbatched(batch, n: int, what: str = "grad_accum"):
    """(B, ...) leaves -> (n, B//n, ...); scalars broadcast.  Used both for
    sequential microbatch accumulation and for the per-pod gradient stack."""
    def one(x):
        if x.ndim == 0:
            return jnp.broadcast_to(x, (n,))
        if x.shape[0] % n:
            raise ValueError(
                f"{what}={n} does not divide batch dim {x.shape[0]}")
        return x.reshape((n, x.shape[0] // n) + x.shape[1:])
    return jax.tree.map(one, batch)


def _kernels_partitionable(mesh) -> bool:
    """GSPMD cannot partition a Mosaic (Pallas TPU) kernel, so a step
    sharded over several devices traces the jnp emulation of every
    contraction instead: the same MX numerics (tests pin fused ==
    emulated), without the fused kernels."""
    return mesh is None or mesh.size == 1


def make_train_step(loss_fn: Callable, opt_cfg: AdamWConfig,
                    tcfg: TrainerConfig, mesh=None, param_specs=None,
                    opt_specs=None, batch_specs=None, monitors=None):
    """loss_fn(params, batch, qcfg) -> (loss, metrics).  Returns a function
    (params, opt_state, batch, step, qcfg[static]) -> (params, opt_state,
    metrics), jitted with qcfg static so interventions recompile cleanly.

    With ``monitors`` (a `repro.guard.MonitorConfig`) the step instead has
    signature (params, opt_state, mon_state, batch, step, qcfg) ->
    (params, opt_state, mon_state, metrics): guard risk signals are
    computed in-jit every step and merged into metrics under ``guard_*``
    keys; the ζ-bound probe (an extra fp32 backward) runs only on probe
    steps behind a `lax.cond`.

    With ``mesh`` the step is jitted with explicit in/out shardings built
    from the given PartitionSpec trees; a "pod" mesh axis additionally
    routes the cross-pod gradient all-reduce through `compressed_psum`
    inside a shard_map over "pod" (data/model stay auto/GSPMD)."""
    accum = max(1, tcfg.grad_accum)
    pod = mesh is not None and "pod" in mesh.axis_names
    fmt = get_format(tcfg.pod_compression) if tcfg.pod_compression else None
    if fmt is not None and not pod:
        raise ValueError(
            "pod_compression is set but the mesh has no 'pod' axis — the "
            "compressed gradient exchange would silently not run; use a "
            "3-dim mesh (--mesh data,model,pod) or unset pod_compression")

    def grads_of(params, batch, qcfg):
        vg = jax.value_and_grad(loss_fn, has_aux=True)
        if accum == 1:
            (loss, metrics), grads = vg(params, batch, qcfg)
            return loss, dict(metrics), grads
        mb = _microbatched(batch, accum)
        first = jax.tree.map(lambda x: x[0], mb)
        rest = jax.tree.map(lambda x: x[1:], mb)
        (l0, m0), g0 = vg(params, first, qcfg)

        def acc(carry, b):
            (loss, metrics), grads = vg(params, b, qcfg)
            return jax.tree.map(
                lambda c, x: c + x.astype(jnp.float32) / accum, carry,
                (loss, dict(metrics), grads)), None

        carry0 = jax.tree.map(lambda x: x.astype(jnp.float32) / accum,
                              (l0, dict(m0), g0))
        (loss, metrics, grads), _ = jax.lax.scan(acc, carry0, rest)
        return loss, metrics, grads

    if pod:
        from repro.parallel import compressed_psum, compression_error_terms
        npod = mesh.shape["pod"]

        def exchange(gs):
            # shard_map body, manual over "pod" only: each pod holds its
            # local mean gradient (leading stack axis of size 1 here).
            # Quantize-then-sum across the slow axis (see parallel/
            # compression.py for why this order keeps the error bounded).
            gs = jax.tree.map(lambda x: jnp.squeeze(x, 0), gs)
            err = jnp.zeros((), jnp.float32)
            if fmt is not None:
                num, den = compression_error_terms(gs, fmt)
                err = jnp.sqrt(jax.lax.psum(num, "pod")
                               / jnp.maximum(jax.lax.psum(den, "pod"),
                                             1e-30))
            gs = compressed_psum(gs, "pod", fmt)
            return jax.tree.map(lambda x: x / npod, gs), err

        def fwd_bwd(params, batch, qcfg):
            # Per-pod gradients via vmap over a pod-sharded stack axis:
            # the model itself stays in the GSPMD (auto) world — XLA's
            # partial-manual mode cannot partition scan-over-layers — and
            # only the elementwise quantize+psum exchange runs manual.
            mb = _microbatched(batch, npod, what="pod")
            # Inside the per-pod region, activation constraints must not
            # pin batch dims to "pod" (each vmap lane is one pod's shard);
            # re-enter the context with "pod" excluded so shard_act uses
            # only the data axis and the compressed psum below stays the
            # only cross-pod traffic.
            from repro.parallel.sharding import activation_sharding

            def pod_grads(b):
                with activation_sharding(mesh, manual=("pod",)):
                    return grads_of(params, b, qcfg)

            loss, metrics, grads = jax.vmap(pod_grads)(mb)
            # Pin each pod's gradient replica to its pod so the exchange
            # is the only cross-pod traffic.
            specs = jax.tree.flatten(
                param_specs, is_leaf=lambda x: isinstance(x, P))[0]
            flat, tdef = jax.tree.flatten(grads)
            grads = tdef.unflatten([
                jax.lax.with_sharding_constraint(
                    g, NamedSharding(mesh, P("pod", *s)))
                for g, s in zip(flat, specs)])
            f = jax.shard_map(exchange, mesh=mesh, in_specs=(P("pod"),),
                              out_specs=(P(), P()), axis_names={"pod"},
                              check_vma=False)
            grads, err = f(grads)
            loss = jnp.mean(loss)
            metrics = jax.tree.map(lambda m: jnp.mean(m, axis=0), metrics)
            if fmt is not None:
                metrics["compression_error"] = err
            return loss, metrics, grads
    else:
        fwd_bwd = grads_of

    def update(params, opt_state, batch, step, qcfg: QuantConfig):
        loss, metrics, grads = fwd_bwd(params, batch, qcfg)
        lr = warmup_cosine(step, tcfg.total_steps, tcfg.peak_lr, tcfg.init_lr,
                           tcfg.end_lr, tcfg.warmup_frac)
        params, opt_state, om = adamw_update(grads, opt_state, params, lr,
                                             opt_cfg)
        metrics.update(om)
        metrics["lr"] = lr
        metrics["loss"] = loss
        return params, opt_state, metrics, grads

    if monitors is None:
        def step_fn(params, opt_state, batch, step, qcfg: QuantConfig):
            params, opt_state, metrics, _ = update(params, opt_state, batch,
                                                   step, qcfg)
            return params, opt_state, metrics
        static, donate = (4,), (0, 1)
        shapes = lambda pl, ol, bl, rep: (
            ((pl, ol, bl, rep), (pl, ol, rep)))
    else:
        from repro.guard import monitor_init, monitor_update

        def step_fn(params, opt_state, mstate, batch, step,
                    qcfg: QuantConfig):
            # the monitor reads the *pre-update* params (LN clamp stats
            # describe the weights the step just trained with), so keep a
            # reference before adamw_update consumes the donated buffers
            p_in = params
            params, opt_state, metrics, grads = update(params, opt_state,
                                                       batch, step, qcfg)
            # fp32 reference backward for the ζ probe; only *executed* on
            # probe steps (the lax.cond lives inside monitor_update)
            probe = lambda: fwd_bwd(p_in, batch, qcfg.to_fp32())[2]
            mstate, sig = monitor_update(
                monitors, mstate, step=step, loss=metrics["loss"],
                gnorm=metrics["grad_norm"], grads=grads, params=p_in,
                qcfg=qcfg, probe_fn=probe)
            for name, v in sig._asdict().items():
                metrics["guard_" + name] = v
            return params, opt_state, mstate, metrics
        static, donate = (5,), (0, 1, 2)
        mrep = lambda rep: jax.tree.map(lambda _: rep,
                                        monitor_init(monitors))
        shapes = lambda pl, ol, bl, rep: (
            ((pl, ol, mrep(rep), bl, rep), (pl, ol, mrep(rep), rep)))

    if not _kernels_partitionable(mesh):
        kernel_step = step_fn

        @functools.wraps(kernel_step)
        def step_fn(*args):
            with use_fused_gemms(False):
                return kernel_step(*args)

    if mesh is None:
        return SegmentFn(step_fn, static_argnums=static,
                         donate_argnums=donate, name="train_step")
    like = lambda specs: jax.tree.map(
        lambda s: NamedSharding(mesh, s), specs,
        is_leaf=lambda x: isinstance(x, P))
    rep = NamedSharding(mesh, P())
    ins, outs = shapes(like(param_specs), like(opt_specs),
                       like(batch_specs), rep)
    return SegmentFn(step_fn, static_argnums=static, donate_argnums=donate,
                     in_shardings=ins, out_shardings=outs,
                     name="train_step")


class Trainer:
    def __init__(self, loss_fn, params, qcfg: QuantConfig,
                 batch_fn: Callable[[int], Any],
                 opt_cfg: Optional[AdamWConfig] = None,
                 tcfg: Optional[TrainerConfig] = None,
                 mesh=None):
        self.tcfg = tcfg or TrainerConfig()
        self.opt_cfg = opt_cfg or AdamWConfig()
        self.loss_fn = loss_fn
        self.batch_fn = batch_fn
        self.qcfg = qcfg
        self.mesh = mesh
        self.params = params
        self.opt_state = adamw_init(params, self.opt_cfg)
        self.step = 0
        self.detector = SpikeDetector(self.tcfg.spike_factor,
                                      self.tcfg.grad_factor)
        self._pspecs = self._ospecs = self._bspecs = None
        self._bshard = None
        if mesh is not None:
            from repro.parallel import (batch_pspecs, param_pspecs,
                                        shardings_like)
            self._pspecs = param_pspecs(self.params, mesh)
            self._ospecs = param_pspecs(self.opt_state, mesh)
            try:
                # only the shapes matter; don't materialize (or fetch) a
                # real batch just to derive PartitionSpecs
                batch0 = jax.eval_shape(batch_fn, 0)
            except Exception:   # batch_fn not traceable (I/O, host code)
                batch0 = batch_fn(0)
            self._bspecs = batch_pspecs(batch0, mesh)
            self._bshard = shardings_like(self._bspecs, mesh)
            self.params = jax.device_put(
                self.params, shardings_like(self._pspecs, mesh))
            self.opt_state = jax.device_put(
                self.opt_state, shardings_like(self._ospecs, mesh))
        self._controller = self._mcfg = self._mstate = None
        if self.tcfg.guard is not None:
            from repro.guard import (MonitorConfig, PrecisionController,
                                     get_policy, monitor_init)
            policy = get_policy(self.tcfg.guard)
            self._controller = PrecisionController(qcfg, policy)
            if not policy.is_scheduled:
                # scheduled policies ignore signals entirely — don't pay
                # for in-jit monitors (or the periodic fp32 ζ backward)
                # that decide() would discard
                self._mcfg = MonitorConfig(
                    probe_every=max(0, self.tcfg.guard_probe_every))
                self._mstate = monitor_init(self._mcfg)
        self._step_fn = make_train_step(loss_fn, self.opt_cfg, self.tcfg,
                                        mesh, self._pspecs, self._ospecs,
                                        self._bspecs, monitors=self._mcfg)
        self.history: List[Dict[str, float]] = []
        self.events: Journal = Journal()
        # live segment numbering: every qcfg transition (guard, recovery,
        # restore adoption) starts a new compiled segment; the index rides
        # checkpoint meta so a resume continues the original numbering
        self._segments = SegmentTracker(qcfg, journal=self.events)
        self.ledger = MemoryLedger(name="trainer")
        self.ledger.account("params", self.params)
        self.ledger.account("opt", self.opt_state)
        self._ckptr = None
        if self.tcfg.ckpt_dir:
            from .checkpoint import Checkpointer
            self._ckptr = Checkpointer(self.tcfg.ckpt_dir,
                                       self.tcfg.keep_ckpts)
        self._recoveries = 0
        self._step_times: List[float] = []
        self._fused_gemms: Optional[bool] = None

    # ---- checkpoint / restore --------------------------------------------
    def _tree(self):
        return {"params": self.params, "opt": self.opt_state}

    def _tree_shardings(self):
        if self.mesh is None:
            return None
        from repro.parallel import shardings_like
        return {"params": shardings_like(self._pspecs, self.mesh),
                "opt": shardings_like(self._ospecs, self.mesh)}

    def checkpoint(self):
        if self._ckptr:
            # one serializer (runtime.journal.checkpoint_meta) builds the
            # meta on the save side and parses it on the restore side, so
            # the two can never drift apart field-by-field; autopilot state
            # rides along so a resume picks up mid-flight (level,
            # hysteresis counters, journal)
            meta = checkpoint_meta(step=self.step, qcfg=self.qcfg,
                                   recoveries=self._recoveries,
                                   controller=self._controller,
                                   segment_index=self._segments.index)
            self._ckptr.save(self.step, self._tree(), meta)

    def restore(self, step: Optional[int] = None,
                adopt_meta: bool = True) -> bool:
        """Load the newest (or given) checkpoint onto the current mesh.

        ``adopt_meta=True`` (resume semantics) also restores the recorded
        QuantConfig and recovery count, warning if the recorded precision
        differs from the live one — otherwise a resume after a mid-run
        intervention would silently train in the pre-intervention format
        (the exact failure the Fig. 7 interventions exist to prevent).
        In-run rollback (`_recover`) passes ``adopt_meta=False``: there the
        in-memory qcfg *is* the intervention and must survive the restore.
        """
        if not self._ckptr:
            return False
        from .checkpoint import latest_step, restore
        self._ckptr.wait()
        s = latest_step(self.tcfg.ckpt_dir) if step is None else step
        if s is None:
            return False
        tree, meta, s = restore(self.tcfg.ckpt_dir, self._tree(), s,
                                shardings=self._tree_shardings())
        self.params, self.opt_state = tree["params"], tree["opt"]
        self.step = s
        if adopt_meta and meta:
            rm = parse_checkpoint_meta(meta)
            if rm.recoveries is not None:
                self._recoveries = rm.recoveries
            if rm.qcfg is not None and rm.qcfg != self.qcfg:
                warnings.warn(
                    f"checkpoint step {s} was written with qcfg "
                    f"[{rm.qcfg.describe()}] but the trainer was "
                    f"constructed with [{self.qcfg.describe()}]; "
                    "adopting the checkpoint's qcfg (mid-run "
                    "intervention preserved)")
                self.events.append({
                    "step": s, "event": "qcfg_restored",
                    "from_qcfg": self.qcfg.describe(),
                    "to_qcfg": rm.qcfg.describe()})
                self.qcfg = rm.qcfg
            if self._controller is not None:
                if rm.guard:
                    self._controller.load_state_dict(rm.guard)
                    self.events.append({
                        "step": s, "event": "guard_restored",
                        "level": self._controller.level,
                        "transitions": len(self._controller.journal),
                        "qcfg": self._controller.qcfg.describe()})
                elif self._controller.qcfg != self.qcfg:
                    # pre-guard checkpoint: adopt the restored scheme as
                    # the controller's baseline instead of desyncing
                    self._controller.rebase(self.qcfg)
            # a restore re-enters the checkpointed segment (no journal
            # record) rather than starting a new one
            self._segments.restore(rm.segment_index, self.qcfg)
        return True

    # ---- recovery policy --------------------------------------------------
    def _recover(self, reason: str) -> bool:
        """Roll back (if possible) + intervene.  Returns whether a rollback
        actually happened — without one the post-spike steps remain applied
        and their metrics must still be accounted for by the caller."""
        # adopt_meta=False: rollback must keep the in-memory qcfg — the
        # intervention applied below is the whole point of the recovery.
        rolled = self.restore(adopt_meta=False)
        old = self.qcfg.describe()
        if self.tcfg.auto_intervention:
            # Applied even with no checkpointer: a forward-fix (precision
            # switch without rollback) still stabilizes per Fig. 7.
            self.qcfg = apply_intervention(self.qcfg,
                                           self.tcfg.auto_intervention)
            if self._controller is not None:
                # the recovery's scheme is the new floor: without a rebase
                # the controller's next transition would recompute from its
                # stale base and silently revert this intervention
                self._controller.rebase(self.qcfg)
        self._recoveries += 1
        self.detector = SpikeDetector(self.tcfg.spike_factor,
                                      self.tcfg.grad_factor)
        if self._mcfg is not None:
            # monitor EMAs describe the poisoned trajectory — restart them
            from repro.guard import monitor_init
            self._mstate = monitor_init(self._mcfg)
        # the segment boundary is journaled before the recovery record so
        # the "recovery" event stays the window's terminal entry
        self._segments.transition(self.step, self.qcfg, reason="recovery")
        self.events.append({
            "step": self.step, "event": "recovery", "reason": reason,
            "rolled_back": rolled, "from_qcfg": old,
            "to_qcfg": self.qcfg.describe()})
        return rolled

    # ---- metric window ----------------------------------------------------
    def _guard_pass(self, pending) -> bool:
        """Feed the window's risk signals to the autopilot — the *first*
        line of defense, evaluated before the spike watchdog sees the
        window.  At most one transition per window; the new scheme takes
        effect at ``self.step`` (the next step to execute), which is the
        step the journal records — a scheduled replay therefore switches
        at exactly the same boundary, bitwise.  Guard transitions survive
        a subsequent rollback (forward-fix semantics, like `_recover`)."""
        if self._controller is None:
            return False
        from repro.guard import signals_from_metrics
        for s, metrics, _ in pending:
            sig = signals_from_metrics(metrics)
            new = self._controller.observe(s, sig,
                                           effective_step=self.step)
            if new is not None:
                self.events.append(dict(self._controller.journal[-1]))
                self.qcfg = new
                self._segments.transition(self.step, new, reason="guard")
                return True
        return False

    def _drain(self, pending) -> tuple:
        """Record a window of (step, metrics, time_s) entries: append
        history, feed the watchdog per step in order.  Stops at the first
        spike; returns (spike reason or None, entries consumed) so the
        caller can decide what the tail means (rollback invalidates it,
        a forward-fix does not)."""
        for i, (s, metrics, dt) in enumerate(pending):
            loss = float(metrics["loss"])
            gnorm = float(metrics["grad_norm"])
            self._step_times.append(dt)
            win = self._step_times[-64:]
            med = sorted(win)[len(win) // 2]
            rec = {"step": s, "loss": loss, "grad_norm": gnorm,
                   "lr": float(metrics["lr"]), "time_s": dt}
            if "compression_error" in metrics:
                rec["compression_error"] = float(
                    metrics["compression_error"])
            for k in ("guard_zeta", "guard_gnorm_ratio", "guard_loss_ratio",
                      "guard_loss_curvature"):
                if k in metrics:
                    rec[k] = float(metrics[k])
            if dt > self.tcfg.straggler_factor * med and len(
                    self._step_times) > 8:
                self.events.append({"step": s, "event": "straggler",
                                    "time_s": dt, "median_s": med})
            self.history.append(rec)
            if self.detector.update(loss, gnorm):
                return f"spike@step{s}: loss={loss:.4g}", i + 1
        return None, len(pending)

    def _mesh_context(self) -> contextlib.ExitStack:
        ctx = contextlib.ExitStack()
        if self.mesh is not None:
            from repro.parallel.sharding import activation_sharding
            ctx.enter_context(self.mesh)
            ctx.enter_context(activation_sharding(self.mesh))
        return ctx

    def lower_step(self):
        """Lower, without running, the step the next ``run`` executes
        (``.compile().as_text()`` shows which kernels it calls)."""
        batch = self.batch_fn(self.step)
        if self._bshard is not None:
            batch = jax.device_put(batch, self._bshard)
        carry = (self.params, self.opt_state) if self._mcfg is None else (
            self.params, self.opt_state, self._mstate)
        with self._mesh_context():
            return self._step_fn.lower(*carry, batch, jnp.asarray(self.step),
                                       self.qcfg)

    # ---- main loop ---------------------------------------------------------
    def run(self, n_steps: Optional[int] = None):
        if self._fused_gemms is None:
            # Latched at the first run: the dispatch decision is baked into
            # _step_fn's jit cache at first trace, so later toggles of
            # use_fused_gemms would not change the executing path.  Recorded
            # so run reports can attribute throughput.
            self._fused_gemms = (fused_gemms_enabled()
                                 and _kernels_partitionable(self.mesh))
        if not self.events or self.events[-1].get("event") != "run_start":
            self.events.append({"step": self.step, "event": "run_start",
                                "fused_gemms": self._fused_gemms,
                                "mesh": dict(self.mesh.shape)
                                if self.mesh is not None else None,
                                "guard": self._controller.policy.name
                                if self._controller is not None else None,
                                "qcfg": self.qcfg.describe()})
        # n_steps=0 must mean "nothing to do" (e.g. --resume of a finished
        # run), not "default to total_steps"
        end = self.step + (self.tcfg.total_steps if n_steps is None
                           else n_steps)
        log_every = max(self.tcfg.log_every, 1)
        window = MetricsWindow()
        aborted = False
        with self._mesh_context():
            window.reset_clock()
            while self.step < end:
                batch = self.batch_fn(self.step)
                if self._bshard is not None:
                    batch = jax.device_put(batch, self._bshard)
                if self._mcfg is None:
                    self.params, self.opt_state, metrics = self._step_fn(
                        self.params, self.opt_state, batch,
                        jnp.asarray(self.step), self.qcfg)
                else:
                    (self.params, self.opt_state, self._mstate,
                     metrics) = self._step_fn(
                        self.params, self.opt_state, self._mstate, batch,
                        jnp.asarray(self.step), self.qcfg)
                window.push(self.step, metrics)
                self.step += 1
                at_ckpt = bool(self._ckptr) \
                    and self.step % self.tcfg.ckpt_every == 0
                if not (at_ckpt or self.step >= end
                        or self.step % log_every == 0):
                    continue
                # One host sync per window (MetricsWindow.drain): steps
                # chain through params, so the last metric being ready
                # means the window finished; per-step time_s is the window
                # wall time amortized (exact when log_every == 1).
                pending = window.drain()
                self._guard_pass(pending)
                recovered = False
                while pending:
                    spike, consumed = self._drain(pending)
                    pending = pending[consumed:]
                    if spike is None:
                        break
                    if self._recoveries >= self.tcfg.max_recoveries:
                        # Terminal: rolling back yet again would replay the
                        # identical data into the identical state — a
                        # livelock, not a recovery.  Abort instead.
                        self.events.append({
                            "step": self.step, "event": "recovery_exhausted",
                            "reason": spike,
                            "recoveries": self._recoveries})
                        aborted = True
                        break
                    recovered = True
                    if self._recover(spike):
                        # rolled back: the tail was computed from a state
                        # that no longer exists — drop it
                        pending = []
                    # no rollback (forward-fix): the tail's updates remain
                    # applied, so keep draining it into history/watchdog
                pending = []
                # exclude recovery/checkpoint host work from the next
                # window's amortized step time
                window.reset_clock()
                if aborted:
                    break
                if at_ckpt and not recovered:
                    self.checkpoint()
        if self._ckptr:
            if not aborted:
                self.checkpoint()
            self._ckptr.wait()
        return self.history
