"""Resilient sweep execution (checkpoint/resume, fault isolation,
validation — `repro.core.resilience`).

The contract under test is *bitwise*: because the batch is prepared once
and every chunk / bisection sub-range is a slice of the same prepared
batch evaluated by the same jitted engine, a resumed (or retried, or
bisected-around-a-poisoned-config) run must reproduce the uninterrupted
`sweep()` / `mc_sweep()` arrays exactly — not within tolerance.  The
CI `resilience_resume` benchmark leg asserts the same property on a
512-configuration grid; here a small grid covers every code path.
"""
import os
import sys

if "jax" not in sys.modules:
    _flags = os.environ.get("XLA_FLAGS", "")
    if "--xla_force_host_platform_device_count" not in _flags:
        os.environ["XLA_FLAGS"] = (
            _flags + " --xla_force_host_platform_device_count=2").strip()

import dataclasses  # noqa: E402
import json  # noqa: E402

import numpy as np  # noqa: E402
import pytest  # noqa: E402

from repro.checkpoint.checkpointer import LEAVES  # noqa: E402
from repro.core import hierarchy as h, placement as pl  # noqa: E402
from repro.core import projections as proj  # noqa: E402
from repro.core.arrivals import EnvelopeSpec  # noqa: E402
from repro.core.hierarchy import SweepValidationError  # noqa: E402
from repro.core.mc_sweep import MCAxes, mc_sweep  # noqa: E402
from repro.core import resilience  # noqa: E402
from repro.core.resilience import (RUN_MANIFEST, CompileError,  # noqa: E402
                                   FaultPlan, InjectedCrash,
                                   ResumeMismatchError, resilient_mc_sweep,
                                   resilient_sweep)
from repro.core.sweep import SweepAxes, sweep  # noqa: E402
from repro.runtime.fault import Backoff  # noqa: E402

SCALE = 0.004
# zero base delay: retry schedules stay instant but keep their counts
NO_WAIT = Backoff(base_s=0.0, max_retries=2)

SWEEP_FIELDS = ("halls_active", "deployed_mw", "p50_stranding",
                "p90_stranding", "final_hall_stranding",
                "final_lineup_stranding", "n_halls_built",
                "final_deployed_mw", "placed_fraction", "initial_dpm",
                "effective_dpm", "total_capex", "provisioned_mw",
                "delivered_tps", "tps_per_provisioned_w",
                "dollars_per_tps")
MC_FIELDS = ("lineup_stranding", "hall_stranding", "deployed_kw",
             "saturated", "placed_a", "placed_b", "ha_capacity_kw",
             "provisioned_mw", "delivered_tps", "tps_per_provisioned_w",
             "dollars_per_tps")


def _env(sc=proj.MED):
    return EnvelopeSpec(demand_scale=SCALE, gpu_scenario=sc,
                        end_year=2028)


def _assert_bitwise(res, ref, fields, rows=None):
    """`rows=None`: whole arrays bitwise-equal.  `rows=mask/index`:
    only those leading-axis rows (the not-quarantined comparison)."""
    for f in fields:
        a, b = np.asarray(getattr(res, f)), np.asarray(getattr(ref, f))
        if rows is not None:
            a, b = a[rows], b[rows]
        np.testing.assert_array_equal(a, b, err_msg=f)


@pytest.fixture(scope="module")
def axes8():
    """8 configurations (2 designs × 2 envelopes × 2 seeds), B=8 so a
    chunk_size of 3 exercises a ragged last chunk."""
    return SweepAxes.product(
        designs=[h.get_design("4N/3"), h.get_design("3+1")],
        envs=[_env(proj.MED), _env(proj.HIGH)], seeds=(0, 1))


@pytest.fixture(scope="module")
def base8(axes8):
    """The uninterrupted one-shot reference result."""
    return sweep(axes8)


@pytest.fixture(scope="module")
def mc_axes3():
    return MCAxes.zip(
        designs=[h.get_design(n) for n in ("4N/3", "3+1", "10N/8")],
        seeds=[11, 12, 13])


MC_KW = dict(n_trials=2, n_events=80, year=2030, scenario=proj.HIGH)


@pytest.fixture(scope="module")
def mc_base3(mc_axes3):
    return mc_sweep(mc_axes3, **MC_KW)


# ---------------------------------------------------------------------------
# resilient_sweep ≡ sweep (no faults)
# ---------------------------------------------------------------------------

class TestResilientEqualsSweep:
    @pytest.mark.parametrize("chunk", [1, 3, 8])
    def test_chunked_bitwise_equals_one_shot(self, axes8, base8, chunk):
        res = resilient_sweep(axes8, chunk_size=chunk)
        _assert_bitwise(res, base8, SWEEP_FIELDS)
        r = res.report
        assert r.n_configs == 8 and r.chunk_size == chunk
        assert r.n_chunks == -(-8 // chunk) == r.chunks_computed
        assert r.chunks_resumed == 0 and not r.quarantined

    def test_default_chunk_is_whole_batch(self, axes8, base8):
        res = resilient_sweep(axes8)
        assert res.report.n_chunks == 1
        _assert_bitwise(res, base8, SWEEP_FIELDS)


# ---------------------------------------------------------------------------
# kill-and-resume
# ---------------------------------------------------------------------------

class TestKillAndResume:
    @pytest.mark.parametrize("crash_after", [0, 1, 2])
    def test_resume_bitwise_after_every_chunk_boundary(
            self, axes8, base8, tmp_path, crash_after):
        """Kill right after each chunk commits (3 chunks of ≤3); the
        resumed result must be bitwise-identical to the uninterrupted
        run, recomputing only the chunks that never committed."""
        ck = str(tmp_path)
        with pytest.raises(InjectedCrash):
            resilient_sweep(axes8, chunk_size=3, checkpoint_dir=ck,
                            fault_plan=FaultPlan(crash_after=crash_after))
        res = resilient_sweep(axes8, chunk_size=3, checkpoint_dir=ck)
        _assert_bitwise(res, base8, SWEEP_FIELDS)
        assert res.report.chunks_resumed == crash_after + 1
        assert res.report.chunks_computed == 3 - (crash_after + 1)

    def test_completed_run_resumes_fully_then_rejects_other_grid(
            self, axes8, base8, tmp_path):
        ck = str(tmp_path)
        resilient_sweep(axes8, chunk_size=3, checkpoint_dir=ck)
        res = resilient_sweep(axes8, chunk_size=3, checkpoint_dir=ck)
        assert res.report.chunks_resumed == 3
        assert res.report.chunks_computed == 0
        _assert_bitwise(res, base8, SWEEP_FIELDS)
        # a different chunk grid (or axes) is a different run: refuse to
        # clobber the directory instead of silently mixing slabs
        with pytest.raises(ResumeMismatchError):
            resilient_sweep(axes8, chunk_size=4, checkpoint_dir=ck)

    def test_torn_manifest_discards_chunks_and_restarts(
            self, axes8, base8, tmp_path):
        """A manifest killed mid-write is unprovable: the chunks are
        discarded and the run starts fresh — still bitwise."""
        ck = str(tmp_path)
        with pytest.raises(InjectedCrash):
            resilient_sweep(axes8, chunk_size=3, checkpoint_dir=ck,
                            fault_plan=FaultPlan(crash_after=1))
        raw = (tmp_path / RUN_MANIFEST).read_text()
        (tmp_path / RUN_MANIFEST).write_text(raw[:len(raw) // 2])
        res = resilient_sweep(axes8, chunk_size=3, checkpoint_dir=ck)
        assert res.report.chunks_resumed == 0
        assert res.report.chunks_computed == 3
        _assert_bitwise(res, base8, SWEEP_FIELDS)
        # the fresh run rewrote a valid manifest
        m = json.loads((tmp_path / RUN_MANIFEST).read_text())
        assert m["fingerprint"] == res.report.fingerprint

    def test_torn_chunk_payload_recomputed(self, axes8, base8, tmp_path):
        """A committed chunk whose payload bytes were torn fails its
        checksum on resume and is recomputed; intact chunks resume."""
        ck = str(tmp_path)
        with pytest.raises(InjectedCrash):
            resilient_sweep(axes8, chunk_size=3, checkpoint_dir=ck,
                            fault_plan=FaultPlan(crash_after=1))
        payload = tmp_path / "step_00000001" / LEAVES
        raw = bytearray(payload.read_bytes())
        raw[-1] ^= 0xFF
        payload.write_bytes(bytes(raw))
        res = resilient_sweep(axes8, chunk_size=3, checkpoint_dir=ck)
        assert res.report.chunks_resumed == 1      # chunk 0 only
        assert res.report.chunks_computed == 2
        _assert_bitwise(res, base8, SWEEP_FIELDS)


# ---------------------------------------------------------------------------
# fault isolation / quarantine
# ---------------------------------------------------------------------------

class TestQuarantine:
    def test_poisoned_config_isolated_others_bitwise(self, axes8, base8):
        """One config that crashes every evaluation: bisection must
        quarantine exactly it; all other rows bitwise unchanged."""
        res = resilient_sweep(axes8, chunk_size=3,
                              fault_plan=FaultPlan(poison=(5,)),
                              backoff=NO_WAIT)
        r = res.report
        assert r.quarantined_indices() == (5,)
        q = r.quarantined[0]
        assert q.reason == "crash" and "poisoned" in q.error
        keep = [i for i in range(8) if i != 5]
        _assert_bitwise(res, base8, SWEEP_FIELDS, rows=keep)
        # the quarantined row carries sentinels in every representation
        assert np.isnan(res.final_deployed_mw[5])
        assert np.isnan(res.deployed_mw[5]).all()
        assert int(res.n_halls_built[5]) == -1
        assert np.isnan(res.total_capex[5])
        assert np.isnan(res.dollars_per_tps[5]).all()

    def test_nan_output_quarantined(self, axes8, base8):
        res = resilient_sweep(axes8, chunk_size=3,
                              fault_plan=FaultPlan(nan=(2,)),
                              backoff=NO_WAIT)
        assert res.report.quarantined_indices() == (2,)
        assert res.report.quarantined[0].reason == "nan-output"
        keep = [i for i in range(8) if i != 2]
        _assert_bitwise(res, base8, SWEEP_FIELDS, rows=keep)
        assert np.isnan(res.placed_fraction[2])

    def test_oom_halves_dispatch_without_losing_rows(self, axes8, base8):
        """Injected OOM on full-chunk dispatches forces one halving;
        every row still completes (no quarantine) and stays bitwise."""
        res = resilient_sweep(axes8, chunk_size=8,
                              fault_plan=FaultPlan(oom={0: 1}),
                              backoff=NO_WAIT)
        assert res.report.oom_halvings >= 1
        assert not res.report.quarantined
        _assert_bitwise(res, base8, SWEEP_FIELDS)

    def test_transient_failure_retried_to_success(self, axes8, base8):
        """A chunk whose first two attempts fail succeeds on the third:
        retries are counted, nothing is quarantined, result bitwise."""
        res = resilient_sweep(axes8, chunk_size=3,
                              fault_plan=FaultPlan(fail={1: 2}),
                              backoff=NO_WAIT)
        assert res.report.retries == 2
        assert not res.report.quarantined
        _assert_bitwise(res, base8, SWEEP_FIELDS)

    def test_quarantine_survives_kill_and_resume(self, axes8, base8,
                                                 tmp_path):
        """Quarantine metadata rides inside the committed chunk slabs:
        a resume re-registers it without re-running the poison."""
        ck = str(tmp_path)
        with pytest.raises(InjectedCrash):
            resilient_sweep(
                axes8, chunk_size=3, checkpoint_dir=ck,
                fault_plan=FaultPlan(poison=(5,), crash_after=1),
                backoff=NO_WAIT)
        res = resilient_sweep(axes8, chunk_size=3, checkpoint_dir=ck)
        r = res.report
        assert r.chunks_resumed == 2 and r.chunks_computed == 1
        assert r.quarantined_indices() == (5,)
        assert r.quarantined[0].reason == "crash"
        keep = [i for i in range(8) if i != 5]
        _assert_bitwise(res, base8, SWEEP_FIELDS, rows=keep)
        assert np.isnan(res.final_deployed_mw[5])


class TestCompileError:
    @pytest.mark.parametrize("front", ["sweep", "mc"])
    def test_compile_error_raises_and_quarantines_nothing(
            self, front, axes8, mc_axes3, tmp_path, monkeypatch):
        """A program the compiler refuses is not a poisoned
        configuration.  Here the kernel path is lowered without interpret
        mode for the CPU backend, which Pallas cannot compile: the
        executor raises `CompileError` at the first range, before any
        dispatch, without retrying, bisecting, quarantining or
        committing a chunk."""
        compiles = []
        real = resilience._RangeEval.compile

        def counting(self, lo, hi):
            compiles.append((lo, hi))
            return real(self, lo, hi)

        monkeypatch.setattr(resilience._RangeEval, "compile", counting)
        ck = str(tmp_path)
        kw = dict(chunk_size=2, checkpoint_dir=ck, backoff=NO_WAIT,
                  use_kernel=True, kernel_interpret=False)
        with pytest.raises(CompileError) as err:
            if front == "sweep":
                resilient_sweep(axes8, **kw)
            else:
                resilient_mc_sweep(mc_axes3, **kw, **MC_KW)
        assert err.value.__cause__ is not None
        assert compiles == [(0, 2)]
        assert not [n for n in os.listdir(ck) if n.startswith("step_")]


# ---------------------------------------------------------------------------
# input validation
# ---------------------------------------------------------------------------

class TestValidation:
    def test_zip_length_mismatch_names_offending_field(self):
        with pytest.raises(SweepValidationError) as e:
            SweepAxes.zip(designs=[h.get_design("4N/3")] * 3,
                          envs=[_env()] * 2)
        assert e.value.field == "envs"
        with pytest.raises(SweepValidationError) as e:
            MCAxes.zip(designs=[h.get_design("4N/3")] * 3,
                       seeds=[1, 2])
        assert e.value.field == "seeds"

    def test_zero_row_design_rejected(self):
        d = dataclasses.replace(h.get_design("4N/3"), ld_rows=0,
                                hd_rows=0)
        with pytest.raises(SweepValidationError, match="zero rows"):
            d.validate()
        with pytest.raises(SweepValidationError, match="zero rows"):
            h.build_topology(d, 1)

    def test_zero_feed_design_rejected(self):
        d = dataclasses.replace(h.get_design("4N/3"), ld_feeds=0)
        with pytest.raises(SweepValidationError) as e:
            h.build_topology(d, 1)
        assert e.value.field == "ld_feeds"
        assert "zero-feed" in str(e.value)

    def test_envelope_catalog(self):
        with pytest.raises(SweepValidationError,
                           match="non-monotone buildout horizon"):
            EnvelopeSpec(start_year=2030, end_year=2028).validate()
        with pytest.raises(SweepValidationError) as e:
            EnvelopeSpec(pod_racks=pl.MAX_POD_RACKS + 1).validate()
        assert e.value.field == "pod_racks"
        with pytest.raises(SweepValidationError) as e:
            EnvelopeSpec(demand_scale=0.0).validate()
        assert e.value.field == "demand_scale"

    def test_axes_catalog(self):
        with pytest.raises(SweepValidationError) as e:
            SweepAxes.zip(designs=[h.get_design("4N/3")],
                          envs=[_env()], policies=[99]).validate()
        assert e.value.field == "policies"
        with pytest.raises(SweepValidationError) as e:
            SweepAxes.zip(designs=[], envs=[]).validate()
        assert e.value.field == "designs"
        with pytest.raises(SweepValidationError) as e:
            MCAxes.zip(designs=[h.get_design("4N/3")],
                       sku_kw=[-1.0]).validate()
        assert e.value.field == "sku_kw"

    def test_sweep_validates_before_compile(self):
        """The engines call `axes.validate()` inside prepare — a bad
        grid dies with the precise error, not a trace-time failure."""
        bad = SweepAxes.zip(designs=[h.get_design("4N/3")],
                            envs=[_env()], policies=[99])
        with pytest.raises(SweepValidationError):
            sweep(bad)
        with pytest.raises(SweepValidationError):
            resilient_sweep(bad)

    def test_error_is_a_value_error(self):
        """Pre-existing callers catching ValueError keep working."""
        assert issubclass(SweepValidationError, ValueError)


# ---------------------------------------------------------------------------
# resilient_mc_sweep
# ---------------------------------------------------------------------------

class TestMCResilience:
    def test_chunked_bitwise_equals_one_shot(self, mc_axes3, mc_base3):
        res = resilient_mc_sweep(mc_axes3, chunk_size=2, **MC_KW)
        _assert_bitwise(res, mc_base3, MC_FIELDS)
        assert res.report.n_chunks == 2 and not res.report.quarantined

    def test_kill_and_resume_bitwise(self, mc_axes3, mc_base3, tmp_path):
        ck = str(tmp_path)
        with pytest.raises(InjectedCrash):
            resilient_mc_sweep(mc_axes3, chunk_size=2, checkpoint_dir=ck,
                               fault_plan=FaultPlan(crash_after=0),
                               **MC_KW)
        res = resilient_mc_sweep(mc_axes3, chunk_size=2,
                                 checkpoint_dir=ck, **MC_KW)
        assert res.report.chunks_resumed == 1
        assert res.report.chunks_computed == 1
        _assert_bitwise(res, mc_base3, MC_FIELDS)

    def test_poisoned_config_isolated(self, mc_axes3, mc_base3):
        res = resilient_mc_sweep(mc_axes3, chunk_size=2,
                                 fault_plan=FaultPlan(poison=(1,)),
                                 backoff=NO_WAIT, **MC_KW)
        assert res.report.quarantined_indices() == (1,)
        keep = [0, 2]
        _assert_bitwise(res, mc_base3, MC_FIELDS, rows=keep)
        assert np.isnan(res.deployed_kw[1]).all()
        assert np.isnan(res.ha_capacity_kw[1])
