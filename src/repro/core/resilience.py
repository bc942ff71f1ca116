"""Resilient sweep execution: checkpoint/resume, fault isolation,
validation (the durability layer under giant grids and the future
sweep service — see docs/reliability.md).

A `giant_grid`-scale run is ~20 minutes of compute; a crash, an OOM, or
one pathological configuration used to lose the whole grid.  This module
wraps the chunked dispatch from the sweep engines with three guarantees:

* **Durable per-chunk checkpointing** — the batch is prepared ONCE
  (`sweep._prepare` / `mc_sweep._mc_prepare`), sliced into fixed chunks,
  and each chunk's raw device slab is committed through the atomic
  `checkpoint.Checkpointer` (write-temp → `os.replace` → fsynced COMMIT
  marker, sha256-checksummed payload).  A run manifest pins the input
  fingerprint (prepared-arg bytes + statics + code salt + chunk grid);
  an interrupted run re-prepares, matches the fingerprint, loads the
  committed chunks and computes only the rest.  Because every chunk is
  a slice of the same prepared batch evaluated by the same jitted
  callable, the resumed result is **bitwise identical** to an
  uninterrupted run (the chunked ≡ one-shot property proven in
  `tests/test_mesh2d.py`).

* **Chunk-level fault isolation** — a failing chunk is retried on an
  exponential `runtime.fault.Backoff` schedule, then bisected so only
  the genuinely poisoned configurations are quarantined: their rows
  become NaN-sentinel results (ints −1, bools False) and the structured
  `RunReport.quarantined` lists them; every other row is bitwise
  unchanged.  NaN appearing in fields that are never legitimately NaN
  (`final_deployed_kw` / `placed_fraction`; MC `deployed_kw`) is treated
  the same way.  OOM (real `RESOURCE_EXHAUSTED` or injected) halves the
  dispatch size — stickily, so later chunks stream at the size that
  fits — while the checkpoint grid keeps the original chunk boundaries.
  A program that fails to lower or compile is not a poisoned
  configuration: every configuration would hit it, so the error is
  raised (`CompileError`) before anything is dispatched or quarantined.

* **Validated inputs** — `axes.validate()` runs before any compile time
  is spent (`SweepValidationError` with the offending field).

`FaultPlan` is the deterministic fault-injection harness the tests and
the `resilience_*` benchmark legs drive: fail chunk k's first j
attempts, inject OOM at a chosen halving depth, poison configurations
(every evaluation of a range containing one crashes), inject NaN rows,
or crash the process right after a chosen chunk commits.

    res = resilient_sweep(axes, chunk_size=128, checkpoint_dir="ckpt/")
    res.report.quarantined, res.report.chunks_resumed, ...
"""
from __future__ import annotations

import hashlib
import json
import os
import shutil
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..checkpoint.checkpointer import Checkpointer, ChecksumError
from ..runtime.fault import Backoff
from . import placement as pl
from .fleet import SimOutputs
from .hierarchy import SweepValidationError
from .mc_sweep import MCAxes, MCResult, _mc_finalize, _mc_prepare, \
    _mc_sweep_jit
from .sweep import SweepAxes, SweepResult, _finalize, _prepare, _sweep_jit

# Version salt folded into the run fingerprint: bump on any change to
# the executor or the engines that affects numerics or slab layout, so
# stale checkpoints can never be resumed into a differently-coded run.
SALT = "resilience-v1"
RUN_MANIFEST = "run_manifest.json"

SWEEP_FIELDS = SimOutputs._fields
MC_FIELDS = ("lineup_stranding", "hall_stranding", "deployed_kw",
             "saturated", "placed_a", "placed_b")
# Quarantine metadata rides inside each chunk's slab dict as plain
# arrays (string-free), so resume reconstructs the report.
_Q_KEYS = ("__q_idx", "__q_reason", "__q_attempts")

REASON_CRASH, REASON_OOM, REASON_NAN = 1, 2, 3
REASONS = {REASON_CRASH: "crash", REASON_OOM: "oom", REASON_NAN: "nan-output"}
_REASON_CODES = {v: k for k, v in REASONS.items()}


# ---------------------------------------------------------------------------
# fault-injection harness
# ---------------------------------------------------------------------------

class SimulatedOOM(MemoryError):
    """Injected out-of-memory failure (stands in for RESOURCE_EXHAUSTED)."""


class InjectedFault(RuntimeError):
    """Injected transient/poison evaluation failure."""


class InjectedCrash(RuntimeError):
    """Injected process death after a chunk commit (kill-and-resume
    tests); escapes `resilient_sweep` by design."""


class CompileError(RuntimeError):
    """The engine program failed to lower or compile (for example a
    kernel the backend's compiler refuses).  That is a fault of the
    program or the backend, not of any configuration, so the executor
    raises it instead of retrying, bisecting or quarantining; the
    compiler's own exception is chained as ``__cause__``."""


class ResumeMismatchError(RuntimeError):
    """The checkpoint directory belongs to a different run (fingerprint
    mismatch): different axes/traces/statics/chunk grid or code salt.
    Clear the directory (or point at a fresh one) to proceed."""


@dataclass
class FaultPlan:
    """Deterministic fault injection for the resilient executor.

    fail:  chunk → n: the chunk's first n full-range attempts raise
           `InjectedFault` (exercises retry/backoff; attempt n+1 wins).
    oom:   chunk → depth: evaluations of any range in that chunk wider
           than `chunk_len // 2**depth` raise `SimulatedOOM`, forcing
           exactly `depth` dispatch-size halvings.
    poison: global config indices; EVERY evaluation of a range
           containing one raises, driving bisection down to quarantine
           exactly those indices.
    nan:   global config indices whose output rows are overwritten with
           NaN after a successful evaluation (quarantined as
           "nan-output" after bisection).
    crash_after: chunk index; `InjectedCrash` is raised right after that
           chunk commits (the kill in kill-and-resume).
    """
    fail: Dict[int, int] = field(default_factory=dict)
    oom: Dict[int, int] = field(default_factory=dict)
    poison: Tuple[int, ...] = ()
    nan: Tuple[int, ...] = ()
    crash_after: Optional[int] = None
    _fail_seen: Dict[int, int] = field(default_factory=dict)
    _oom_seen: Dict[int, int] = field(default_factory=dict)

    def before_eval(self, chunk: int, lo: int, hi: int,
                    chunk_lo: int, chunk_hi: int) -> None:
        if lo == chunk_lo and hi == chunk_hi:
            seen = self._fail_seen.get(chunk, 0)
            if seen < self.fail.get(chunk, 0):
                self._fail_seen[chunk] = seen + 1
                raise InjectedFault(
                    f"injected failure: chunk {chunk} attempt {seen + 1}")
        depth = self.oom.get(chunk, 0)
        if depth and hi - lo > (chunk_hi - chunk_lo) // (1 << depth):
            raise SimulatedOOM(
                f"injected OOM: chunk {chunk} range [{lo}, {hi})")
        bad = [p for p in self.poison if lo <= p < hi]
        if bad:
            raise InjectedFault(
                f"poisoned configuration(s) {bad} in range [{lo}, {hi})")

    def after_eval(self, lo: int, hi: int, slab: Dict[str, np.ndarray]):
        rows = [p - lo for p in self.nan if lo <= p < hi]
        if rows:
            slab = dict(slab)
            for name, arr in slab.items():
                if np.issubdtype(arr.dtype, np.floating):
                    arr = arr.copy()
                    arr[rows] = np.nan
                    slab[name] = arr
        return slab

    def after_commit(self, chunk: int) -> None:
        if self.crash_after is not None and chunk == self.crash_after:
            raise InjectedCrash(
                f"injected crash after committing chunk {chunk}")


# ---------------------------------------------------------------------------
# run report
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class QuarantinedConfig:
    """One quarantined configuration (NaN-sentinel row in the result)."""
    index: int           # global configuration index
    reason: str          # "crash" | "oom" | "nan-output"
    error: str           # exception text ("" when reloaded from disk)
    attempts: int        # evaluation attempts spent on this config


@dataclass
class RunReport:
    """What the resilient executor did (attached as `result.report`)."""
    n_configs: int
    chunk_size: int
    n_chunks: int
    fingerprint: str
    chunks_computed: int = 0
    chunks_resumed: int = 0
    retries: int = 0
    oom_halvings: int = 0
    quarantined: List[QuarantinedConfig] = field(default_factory=list)

    def quarantined_indices(self) -> Tuple[int, ...]:
        return tuple(sorted(q.index for q in self.quarantined))


# ---------------------------------------------------------------------------
# fingerprint + manifest
# ---------------------------------------------------------------------------

def _fingerprint(args, statics: dict, B: int, chunk_size: int) -> str:
    """sha256 over the prepared input batch, the static compile knobs,
    the chunk grid and the code salt — everything the per-chunk slabs
    depend on.  Matching fingerprints ⇒ committed chunks are verbatim
    slices of the run being resumed."""
    h = hashlib.sha256()
    h.update(SALT.encode())
    h.update(f"B={B};chunk={chunk_size}".encode())
    h.update(repr(sorted(statics.items(), key=lambda kv: kv[0])).encode())
    for leaf in jax.tree.leaves(args):
        a = np.asarray(leaf)
        h.update(str(a.dtype).encode())
        h.update(str(a.shape).encode())
        h.update(a.tobytes())
    return h.hexdigest()


def _clear_chunks(directory: str) -> None:
    for name in os.listdir(directory):
        if name.startswith("step_"):
            shutil.rmtree(os.path.join(directory, name),
                          ignore_errors=True)


def _open_run(directory: str, fingerprint: str, B: int, chunk_size: int,
              n_chunks: int) -> bool:
    """Create or match the run manifest.  Returns True when committed
    chunks may be resumed (valid manifest, same fingerprint).  A
    corrupt/alien manifest discards any existing chunks and starts
    fresh; a well-formed manifest for a *different* run raises
    `ResumeMismatchError` instead of silently clobbering it."""
    os.makedirs(directory, exist_ok=True)
    path = os.path.join(directory, RUN_MANIFEST)
    if os.path.exists(path):
        try:
            with open(path) as f:
                m = json.load(f)
            ok = isinstance(m, dict) and isinstance(m.get("fingerprint"), str)
        except (json.JSONDecodeError, OSError):
            m, ok = None, False
        if ok:
            if m["fingerprint"] == fingerprint:
                return True
            raise ResumeMismatchError(
                f"{directory} holds a different run (fingerprint "
                f"{m['fingerprint'][:12]}… ≠ {fingerprint[:12]}…); clear "
                f"it or use a fresh checkpoint_dir")
        _clear_chunks(directory)        # torn manifest ⇒ chunks unprovable
    elif any(n.startswith("step_") for n in os.listdir(directory)):
        _clear_chunks(directory)        # chunks without a manifest
    meta = {"fingerprint": fingerprint, "salt": SALT, "n_configs": B,
            "chunk_size": chunk_size, "n_chunks": n_chunks}
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(meta, f)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)               # atomic manifest publish
    return False


# ---------------------------------------------------------------------------
# chunk executor
# ---------------------------------------------------------------------------

def _is_oom(e: BaseException) -> bool:
    return isinstance(e, MemoryError) or "RESOURCE_EXHAUSTED" in str(e)


class _ChunkExecutor:
    """Evaluate `B` configurations in chunks with checkpointing, retry,
    bisection quarantine, and OOM halving.  `ranges` is a `_RangeEval`
    over the globally prepared batch: `compile(lo, hi)` returns the
    dispatch for configurations `[lo, hi)`, and `shapes(lo, hi)` its
    output shapes; `fields` orders the output leaves into the slab dict;
    NaN in a `detect` field marks a poisoned row."""

    def __init__(self, ranges: "_RangeEval", fields: Sequence[str],
                 detect: Sequence[str], B: int, chunk_size: int,
                 checkpoint_dir: Optional[str], plan: Optional[FaultPlan],
                 backoff: Optional[Backoff]):
        self.ranges = ranges
        self.fields = tuple(fields)
        self.detect = tuple(detect)
        self.B = B
        self.chunk = max(1, min(int(chunk_size), B))
        self.n_chunks = -(-B // self.chunk)
        self.plan = plan if plan is not None else FaultPlan()
        self.backoff = backoff if backoff is not None else Backoff()
        self.eval_size = self.chunk     # sticky OOM-halved dispatch width
        self.ckpt = (Checkpointer(checkpoint_dir, keep=10 ** 9)
                     if checkpoint_dir else None)

    # ---- slab helpers ----
    def _to_slab(self, out) -> Dict[str, np.ndarray]:
        leaves = out if isinstance(out, tuple) and not hasattr(out, "_fields") \
            else [getattr(out, f) for f in self.fields]
        return {f: np.asarray(x) for f, x in zip(self.fields, leaves)}

    def _nan_slab(self, lo: int, hi: int) -> Dict[str, np.ndarray]:
        """Sentinel slab for quarantined rows: floats NaN, ints −1,
        bools False.  Shapes come from `jax.eval_shape` (no compile)."""
        shapes = self.ranges.shapes(lo, hi)
        leaves = (shapes if isinstance(shapes, tuple)
                  and not hasattr(shapes, "_fields")
                  else [getattr(shapes, f) for f in self.fields])
        slab = {}
        for f, s in zip(self.fields, leaves):
            dt = np.dtype(s.dtype)
            if np.issubdtype(dt, np.floating):
                fill = np.nan
            elif dt == np.bool_:
                fill = False
            else:
                fill = -1
            slab[f] = np.full(s.shape, fill, dt)
        return slab

    def _concat(self, slabs: Sequence[Dict[str, np.ndarray]]):
        return {f: np.concatenate([s[f] for s in slabs])
                for f in self.fields}

    def _bad_rows(self, slab: Dict[str, np.ndarray]) -> np.ndarray:
        """Rows whose never-NaN fields came back NaN (poisoned output).
        Only `detect` fields are scanned — quantile/metric columns carry
        legitimate NaN sentinels."""
        bad = None
        for f in self.detect:
            v = np.isnan(slab[f])
            v = v.reshape(v.shape[0], -1).any(axis=1) if v.ndim > 1 else v
            bad = v if bad is None else (bad | v)
        return bad

    # ---- fault-isolated evaluation ----
    def _quarantine(self, report: RunReport, idx: int, reason: int,
                    error: str, attempts: int):
        report.quarantined.append(QuarantinedConfig(
            index=idx, reason=REASONS[reason], error=error,
            attempts=attempts))

    def _eval_range(self, report: RunReport, chunk: int, lo: int, hi: int,
                    chunk_lo: int, chunk_hi: int, retries: int):
        """Evaluate `[lo, hi)` with retry → bisection → quarantine."""
        attempt = 0
        while True:
            try:
                self.plan.before_eval(chunk, lo, hi, chunk_lo, chunk_hi)
                try:
                    dispatch = self.ranges.compile(lo, hi)
                except Exception as e:  # noqa: BLE001 — classified here
                    if _is_oom(e):      # program too large: halve below
                        raise
                    raise CompileError(
                        f"the engine program failed to compile for "
                        f"configurations [{lo}, {hi}): {e}") from e
                slab = self._to_slab(dispatch())
                slab = self.plan.after_eval(lo, hi, slab)
                bad = self._bad_rows(slab)
                if not bad.any():
                    return slab
                if hi - lo == 1:
                    self._quarantine(report, lo, REASON_NAN,
                                     "NaN in non-NaN output field",
                                     attempt + 1)
                    return self._nan_slab(lo, hi)
                # NaN output is deterministic — bisect without retries
                mid = (lo + hi) // 2
                return self._concat([
                    self._eval_range(report, chunk, lo, mid, chunk_lo,
                                     chunk_hi, 0),
                    self._eval_range(report, chunk, mid, hi, chunk_lo,
                                     chunk_hi, 0)])
            except (InjectedCrash, CompileError):
                raise
            except Exception as e:      # noqa: BLE001 — isolate anything
                if _is_oom(e):
                    report.oom_halvings += 1
                    self.eval_size = max(self.eval_size // 2, 1)
                    if hi - lo == 1:
                        self._quarantine(report, lo, REASON_OOM, str(e),
                                         attempt + 1)
                        return self._nan_slab(lo, hi)
                    mid = (lo + hi) // 2
                    return self._concat([
                        self._eval_range(report, chunk, lo, mid, chunk_lo,
                                         chunk_hi, retries),
                        self._eval_range(report, chunk, mid, hi, chunk_lo,
                                         chunk_hi, retries)])
                if attempt < retries:
                    self.backoff.sleep(attempt)
                    attempt += 1
                    report.retries += 1
                    continue
                if hi - lo == 1:
                    self._quarantine(report, lo, REASON_CRASH, str(e),
                                     attempt + 1)
                    return self._nan_slab(lo, hi)
                # retries exhausted on a multi-config range: bisect to
                # isolate the poisoned configuration(s); halves get no
                # further retries (the transient budget is spent)
                mid = (lo + hi) // 2
                return self._concat([
                    self._eval_range(report, chunk, lo, mid, chunk_lo,
                                     chunk_hi, 0),
                    self._eval_range(report, chunk, mid, hi, chunk_lo,
                                     chunk_hi, 0)])

    def _eval_chunk(self, report: RunReport, c: int, lo: int, hi: int):
        """One chunk, streamed at the (possibly OOM-halved) dispatch
        width."""
        parts, pos = [], lo
        while pos < hi:
            end = min(pos + self.eval_size, hi)
            parts.append(self._eval_range(
                report, c, pos, end, lo, hi,
                retries=self.backoff.max_retries))
            pos = end
        return parts[0] if len(parts) == 1 else self._concat(parts)

    # ---- the run ----
    def run(self):
        """Returns `(slab, report)` with `slab` the concatenated
        `[B, …]` field dict."""
        report = RunReport(n_configs=self.B, chunk_size=self.chunk,
                           n_chunks=self.n_chunks, fingerprint="")
        resume_ok, done = False, set()
        if self.ckpt is not None:
            fp = self._run_fingerprint
            report.fingerprint = fp
            resume_ok = _open_run(self.ckpt.dir, fp, self.B, self.chunk,
                                  self.n_chunks)
            if resume_ok:
                done = set(self.ckpt.all_steps())

        slabs = []
        for c in range(self.n_chunks):
            lo, hi = c * self.chunk, min((c + 1) * self.chunk, self.B)
            slab = None
            if resume_ok and c in done:
                try:
                    leaves, _meta = self.ckpt.load(step=c, verify=True)
                    slab = dict(zip(sorted(self.fields + _Q_KEYS), leaves))
                    for q_i, q_r, q_a in zip(slab["__q_idx"],
                                             slab["__q_reason"],
                                             slab["__q_attempts"]):
                        self._quarantine(report, int(q_i), int(q_r), "",
                                         int(q_a))
                    report.chunks_resumed += 1
                except Exception:   # ChecksumError/torn read ⇒ recompute
                    slab = None
            if slab is None:
                n_q = len(report.quarantined)
                slab = self._eval_chunk(report, c, lo, hi)
                report.chunks_computed += 1
                new_q = report.quarantined[n_q:]
                slab["__q_idx"] = np.asarray(
                    [q.index for q in new_q], np.int64)
                slab["__q_reason"] = np.asarray(
                    [_REASON_CODES[q.reason] for q in new_q], np.int8)
                slab["__q_attempts"] = np.asarray(
                    [q.attempts for q in new_q], np.int32)
                if self.ckpt is not None:
                    self.ckpt.save(c, slab, blocking=True)
                    self.plan.after_commit(c)
                else:
                    self.plan.after_commit(c)
            slabs.append(slab)
        return self._concat(slabs), report

    _run_fingerprint: str = ""          # set by the front doors


# ---------------------------------------------------------------------------
# front doors
# ---------------------------------------------------------------------------

class _RangeEval:
    """Range evaluator over the globally prepared batch.  Compiling
    and dispatching are separate steps, so the executor can tell a
    program the compiler refuses from a configuration that crashes.
    Executables are kept per dispatch width: every range of one width
    has the same shapes, and so shares one executable.

    A width-1 vmap compiles a degenerate batch whose accumulation order
    differs bitwise from wider dispatches (observed on XLA:CPU), so
    single-config ranges duplicate their row to width 2 and keep row 0 —
    bitwise identical to the same row inside any wider dispatch."""

    def __init__(self, args, jit_fn, statics: dict):
        self.args, self.jit_fn, self.statics = args, jit_fn, statics
        self._compiled: Dict[int, Callable] = {}

    def _slice(self, lo: int, hi: int):
        if hi - lo == 1:
            idx = jnp.asarray([lo, lo])
            return jax.tree.map(lambda x: x[idx], self.args)
        return jax.tree.map(lambda x: x[lo:hi], self.args)

    def compile(self, lo: int, hi: int) -> Callable:
        """Lower and compile for `[lo, hi)` (raises what the compiler
        raises); returns the zero-argument dispatch of that range."""
        sl = self._slice(lo, hi)
        width = max(hi - lo, 2)
        if width not in self._compiled:
            self._compiled[width] = self.jit_fn.lower(
                *sl, **self.statics).compile()
        exe = self._compiled[width]
        if hi - lo == 1:
            return lambda: jax.tree.map(lambda x: x[:1], exe(*sl))
        return lambda: exe(*sl)

    def shapes(self, lo: int, hi: int):
        """Output shapes of `[lo, hi)` from `jax.eval_shape` (no
        compile)."""
        out = jax.eval_shape(lambda: self.jit_fn(*self._slice(lo, hi),
                                                 **self.statics))
        return jax.tree.map(
            lambda s: jax.ShapeDtypeStruct((hi - lo,) + s.shape[1:],
                                           s.dtype), out)


def _mask_rows(report: RunReport, *arrays: np.ndarray) -> None:
    """NaN the derived float columns of quarantined rows (the raw slab
    already carries sentinels; `_finalize` recomputes per-design cost
    columns that must not survive for quarantined configurations)."""
    idx = list(report.quarantined_indices())
    if not idx:
        return
    for a in arrays:
        if a is not None and np.issubdtype(np.asarray(a).dtype,
                                           np.floating):
            a[idx] = np.nan


def resilient_sweep(axes: SweepAxes, chunk_size: int | None = None,
                    checkpoint_dir: str | None = None,
                    fault_plan: FaultPlan | None = None,
                    backoff: Backoff | None = None,
                    harvest: bool = True, mature_months: int = 12,
                    n_halls_max: int = 0, traces=None, models=None,
                    metric_year: int | None = None,
                    use_kernel: bool | None = None,
                    kernel_interpret: bool = False,
                    exact_quantiles: bool = True,
                    quantile_bins: int | None = None) -> SweepResult:
    """`sweep.sweep` behind the resilient chunk executor.

    The batch is prepared once, evaluated chunk-by-chunk through the
    unsharded jitted engine (slices of one prepared batch ⇒ bitwise
    identity with the one-shot result regardless of chunk boundaries,
    resumes, or bisection descents), and optionally checkpointed per
    chunk.  Returns a `SweepResult` whose `report` is the `RunReport`;
    quarantined configurations carry NaN-sentinel rows.  Multi-device
    sharding stays with `sweep.sharded_sweep` — durability and mesh
    dispatch compose at the service layer, not here.

    Args beyond `sweep.sweep`:
        chunk_size: configurations per checkpointed chunk (default: the
            whole batch as one chunk).
        checkpoint_dir: directory for the run manifest + per-chunk
            checkpoints; None disables durability (isolation/validation
            still apply).  Resuming into a directory whose manifest
            fingerprint does not match raises `ResumeMismatchError`.
        fault_plan: deterministic fault injection (tests/benchmarks).
        backoff: retry schedule for failing chunks (default
            `runtime.fault.Backoff()`).
    """
    args, months, topos, X_pad, with_pods, pod_len, hd_scan = _prepare(
        axes, n_halls_max, traces)
    statics = dict(harvest=harvest, mature_months=mature_months,
                   with_pods=with_pods, pod_scan_len=pod_len,
                   hd_scan=hd_scan,
                   use_kernel=pl.resolve_use_kernel(use_kernel),
                   kernel_interpret=kernel_interpret,
                   exact_quantiles=exact_quantiles,
                   quantile_bins=quantile_bins)
    B = len(axes)
    chunk = chunk_size if chunk_size is not None else B

    ex = _ChunkExecutor(_RangeEval(args, _sweep_jit, statics),
                        SWEEP_FIELDS,
                        detect=("final_deployed_kw", "placed_fraction"),
                        B=B, chunk_size=chunk,
                        checkpoint_dir=checkpoint_dir, plan=fault_plan,
                        backoff=backoff)
    if checkpoint_dir:
        ex._run_fingerprint = _fingerprint(args, statics, B, ex.chunk)
    slab, report = ex.run()
    out = SimOutputs(**{f: slab[f] for f in SWEEP_FIELDS})
    res = _finalize(out, axes, months, topos, X_pad, mature_months,
                    models=models, metric_year=metric_year)
    _mask_rows(report, res.initial_dpm, res.effective_dpm,
               res.total_capex, res.provisioned_mw, res.delivered_tps,
               res.tps_per_provisioned_w, res.dollars_per_tps)
    res.report = report
    return res


def resilient_mc_sweep(axes: MCAxes, chunk_size: int | None = None,
                       checkpoint_dir: str | None = None,
                       fault_plan: FaultPlan | None = None,
                       backoff: Backoff | None = None,
                       n_trials: int = 32, n_events: int = 600,
                       year: int = 2028, scenario: str = "med",
                       gpu_power_share: float = 0.6, pod_racks: int = 1,
                       quantum_racks: int = 10, la_fraction: float = 0.0,
                       harvest: bool = True, single_sku_gpu: bool = False,
                       refill_events: int | None = None, models=None,
                       use_kernel: bool | None = None,
                       kernel_interpret: bool = False) -> MCResult:
    """`mc_sweep.mc_sweep` behind the resilient chunk executor (see
    `resilient_sweep`; chunks slice the configuration axis, trials ride
    inside their configuration)."""
    args, statics = _mc_prepare(axes, n_trials, n_events, year, scenario,
                                gpu_power_share, pod_racks, quantum_racks,
                                la_fraction, single_sku_gpu, refill_events)
    kw = dict(harvest=harvest,
              use_kernel=pl.resolve_use_kernel(use_kernel),
              kernel_interpret=kernel_interpret, **statics)
    B = len(axes)
    chunk = chunk_size if chunk_size is not None else B

    ex = _ChunkExecutor(_RangeEval(args, _mc_sweep_jit, kw), MC_FIELDS,
                        detect=("deployed_kw",),
                        B=B, chunk_size=chunk,
                        checkpoint_dir=checkpoint_dir, plan=fault_plan,
                        backoff=backoff)
    if checkpoint_dir:
        ex._run_fingerprint = _fingerprint(args, kw, B, ex.chunk)
    slab, report = ex.run()
    out = tuple(slab[f] for f in MC_FIELDS)
    res = _mc_finalize(out, axes, models=models, year=year,
                       scenario=scenario,
                       gpu_share=1.0 if single_sku_gpu else gpu_power_share,
                       pod_racks=pod_racks)
    _mask_rows(report, res.ha_capacity_kw, res.provisioned_mw,
               res.delivered_tps, res.tps_per_provisioned_w,
               res.dollars_per_tps)
    res.report = report
    return res
