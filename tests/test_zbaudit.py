"""zbaudit suite tests: every pass proves it fires on a seeded
anti-pattern (positive) and stays quiet on the sanctioned idiom
(negative); plus the baseline ratchet, the HBM model vs measured
device-buffer bytes, the donation parity pins the boundary pass forced
on ``kernel.tick`` / ``engine.due_probe``, the runtime recompile guard,
and the live-tree-clean gate pin (the exact CI invocation).

Fixtures go through :func:`tools.zbaudit.audit_program`, which builds an
``AuditedEntry`` WITHOUT touching the jit registry — so nothing here can
trip the coverage pass on the live tree.
"""

import dataclasses
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from zeebe_tpu import tpu as _tpu  # noqa: F401  (enables x64)
from zeebe_tpu.tpu import (
    batch as rb,
    engine as engine_mod,
    kernel,
    state as state_mod,
)

from tools.zbaudit import audit, audit_program, load_budget
from tools.zbaudit import passes as passes_mod
from tools.zbaudit.core import write_audit_baseline
from tools.zblint.engine import Finding, apply_baseline, load_baseline

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def rules_of(findings):
    return {f.rule for f in findings}


def f32(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.float32)


NOW = jax.ShapeDtypeStruct((), jnp.int64)


# -- seeded anti-patterns ----------------------------------------------------

class TestDtypeFlow:
    def test_f64_leak_fires(self):
        def leaky(x):
            return jnp.asarray(x, jnp.float64) * 2.0

        entry = audit_program("fixture.f64", leaky, f32(8))
        result = audit(passes=["dtype-flow"], entries=[entry], budget={})
        assert "dtype-f64" in rules_of(result.findings)

    def test_f32_program_is_quiet(self):
        entry = audit_program("fixture.f32", lambda x: x * 2.0, f32(8))
        result = audit(passes=["dtype-flow"], entries=[entry], budget={})
        assert result.findings == []

    def test_i64_ratchet_fires_over_budget(self):
        def keys(k):
            return k + jnp.int64(1)

        entry = audit_program(
            "fixture.i64", keys, jax.ShapeDtypeStruct((8,), jnp.int64)
        )
        budget = {"dtype": {"i64_budget": {"fixture.i64": 0}}}
        result = audit(passes=["dtype-flow"], entries=[entry], budget=budget)
        assert "dtype-i64" in rules_of(result.findings)

    def test_i64_under_budget_emits_ratchet_hint(self):
        entry = audit_program(
            "fixture.i64", lambda k: k + jnp.int64(1),
            jax.ShapeDtypeStruct((8,), jnp.int64),
        )
        budget = {"dtype": {"i64_budget": {"fixture.i64": 100}}}
        result = audit(passes=["dtype-flow"], entries=[entry], budget=budget)
        assert result.findings == []
        assert result.report["dtype"]["ratchet_hints"]


class TestTableI64:
    """``table-i64``: no 64-bit integer array of table size in a served
    program — as an input, an output, or anywhere inside."""

    TABLE, WAVE, FLOOR = 4096, 64, 1024

    @staticmethod
    def _hits(fn, *args):
        from tools.zbaudit.passes import oversized_i64

        return oversized_i64(jax.jit(fn).trace(*args).jaxpr, TestTableI64.FLOOR)

    def _i64(self, *shape):
        return jax.ShapeDtypeStruct(shape, jnp.int64)

    def _i32(self, *shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32)

    def test_int64_table_input_and_output_fire(self):
        hits = self._hits(lambda t: t, self._i64(self.TABLE))
        assert any(h.startswith("input: int64") for h in hits)
        assert any(h.startswith("output: int64") for h in hits)

    def test_table_sized_int64_intermediate_fires(self):
        """Planes in, planes out, but int64 of the whole table between."""
        def relaid(planes):
            keys = jax.lax.bitcast_convert_type(planes, jnp.int64)  # [T]
            return jax.lax.bitcast_convert_type(keys + 5, jnp.int32)

        hits = self._hits(relaid, self._i32(self.TABLE, 2))
        assert hits and not any(h.startswith(("input", "output")) for h in hits)
        assert any("bitcast_convert_type: int64[4096]" in h for h in hits)

    def test_int64_inside_a_loop_body_fires(self):
        def looped(planes):
            def body(i, p):
                k = jax.lax.bitcast_convert_type(p, jnp.int64) + 1
                return jax.lax.bitcast_convert_type(k, jnp.int32)

            return jax.lax.fori_loop(0, 3, body, planes)

        assert self._hits(looped, self._i32(self.TABLE, 2))

    def test_uint64_counts_too(self):
        hits = self._hits(
            lambda t: t + jnp.uint64(1),
            jax.ShapeDtypeStruct((self.TABLE,), jnp.uint64),
        )
        assert any("uint64" in h for h in hits)

    def test_wave_sized_int64_of_gathered_plane_rows_is_quiet(self):
        """What the step does: plane rows gathered, int64 of the wave."""
        def step(planes, slots, keys):
            rows = planes[slots]                                  # [B, 2]
            k = jax.lax.bitcast_convert_type(rows, jnp.int64)     # [B]
            new = jax.lax.bitcast_convert_type(k + keys, jnp.int32)
            return planes.at[slots].set(new), k

        assert self._hits(
            step, self._i32(self.TABLE, 2), self._i32(self.WAVE),
            self._i64(self.WAVE),
        ) == []

    def test_the_served_programs_hold_no_table_sized_int64(self):
        """The pass itself, on the live tree at the budget's shape: the
        step (every specialisation on), the tick, the due probe and the
        credit flush."""
        result = audit(passes=["table-i64"], entries=[])
        assert result.findings == []
        per = result.report["table-i64"]
        assert per["config"]["capacity"] >= 1 << 20
        assert per["floor_elements"] == per["config"]["capacity"] // 8
        for name in ("kernel.step", "kernel.tick", "engine.due_probe",
                     "engine.credit_flush"):
            assert per[name] == 0

    def test_a_table_scan_through_int64_would_fire(self, monkeypatch):
        """The guard cannot rot: let one whole-column predicate go back
        through int64 and the pass names every program that runs it."""
        from zeebe_tpu.tpu import state as state_mod

        def col_neg_via_int64(planes, col=0):
            words = planes[:, 2 * col : 2 * col + 2]
            return jax.lax.bitcast_convert_type(words, jnp.int64) < 0

        monkeypatch.setattr(state_mod, "col_neg", col_neg_via_int64)
        monkeypatch.setattr(kernel, "col_neg", col_neg_via_int64)
        # jit keeps traces by signature: neither reuse a clean one here
        # nor leave this one behind
        jax.clear_caches()
        try:
            result = audit(passes=["table-i64"], entries=[])
        finally:
            jax.clear_caches()
        assert rules_of(result.findings) == {"table-i64"}
        assert {f.message.split(":")[0] for f in result.findings} == {
            "kernel.step", "kernel.tick", "engine.due_probe"
        }


class TestBoundary:
    def test_undonated_state_arg_fires(self):
        def step(state, now):
            return state + now

        entry = audit_program(
            "fixture.undonated", step, f32(64), NOW, state_args=(0,),
        )
        result = audit(passes=["boundary"], entries=[entry], budget={})
        assert "boundary-donation" in rules_of(result.findings)

    def test_donated_passthrough_is_quiet_and_aliased(self):
        def step(state, now):
            return state, jnp.sum(state) + now

        entry = audit_program(
            "fixture.donated", step, f32(64), NOW,
            state_args=(0,), donate_argnums=(0,),
        )
        result = audit(passes=["boundary"], entries=[entry], budget={})
        assert result.findings == []
        assert result.report["boundary"]["fixture.donated"][
            "alias_materialized"
        ]

    def test_donation_without_aliasing_fires(self):
        # output shape differs from the donated arg: XLA cannot alias,
        # the declared donation buys nothing
        def shrink(state):
            return jnp.sum(state)

        entry = audit_program(
            "fixture.noalias", shrink, f32(64),
            state_args=(0,), donate_argnums=(0,),
        )
        result = audit(passes=["boundary"], entries=[entry], budget={})
        assert "boundary-alias" in rules_of(result.findings)

    def test_host_callback_fires(self):
        def hostly(x):
            return jax.pure_callback(
                lambda a: a, jax.ShapeDtypeStruct((8,), jnp.float32), x
            )

        entry = audit_program("fixture.callback", hostly, f32(8))
        result = audit(passes=["boundary"], entries=[entry], budget={})
        assert "boundary-callback" in rules_of(result.findings)

    def test_suppressed_donation_gap_is_quiet(self):
        entry = audit_program(
            "fixture.waived", lambda s, n: s + n, f32(64), NOW,
            state_args=(0,), suppress=("boundary-donation",),
        )
        result = audit(passes=["boundary"], entries=[entry], budget={})
        assert result.findings == []

    # -- wave_io: what a step call moves besides its state ------------------

    @staticmethod
    def _step_like(extra_out=0, wide=False):
        """(graph, state, wave pair, now) -> (state, pair, stats[, extra])."""
        def step(graph, state, wave, now):
            out = (wave[0] + graph[0], wave[1])
            stats = jnp.stack([jnp.sum(wave[0], dtype=jnp.int32)] * 2)
            extra = tuple(
                wave[0][:, i].astype(jnp.int64 if wide else jnp.int32)
                for i in range(extra_out)
            )
            return (state + now.astype(jnp.float32), out, stats) + extra

        wave = (
            jax.ShapeDtypeStruct((16, 5), jnp.int32),
            jax.ShapeDtypeStruct((16, 3), jnp.int8),
        )
        graph = (jax.ShapeDtypeStruct((5,), jnp.int32),) * 3
        return audit_program(
            "fixture.step", step, graph, f32(64), wave, NOW,
            state_args=(1,), donate_argnums=(1,),
        )

    _IO_BUDGET = {"boundary": {"wave_io": {"fixture.step": {
        "resident_args": [0], "arrays_in": 2, "arrays_out": 3,
    }}}}

    def test_pair_in_and_pair_plus_stats_out_is_quiet(self):
        entry = self._step_like()
        result = audit(
            passes=["boundary"], entries=[entry], budget=self._IO_BUDGET
        )
        assert result.findings == []
        assert result.report["boundary"]["fixture.step"]["wave_io"] == {
            "arrays_in": 2, "scalars_in": 1, "arrays_out": 3, "wide": [],
        }

    @pytest.mark.parametrize("wide", [False, True], ids=["i32", "i64"])
    def test_a_further_result_fires(self, wide):
        entry = self._step_like(extra_out=1, wide=wide)
        result = audit(
            passes=["boundary"], entries=[entry], budget=self._IO_BUDGET
        )
        assert rules_of(result.findings) == {"boundary-wave-io"}
        message = result.findings[0].message
        assert "arrays_out 4 > 3" in message
        assert ("64-bit arrays cross" in message) == wide

    def test_the_served_step_programs_are_inside_the_budget(self):
        """``kernel.step`` as lowered for the audit takes the packed pair
        and returns the pair and one stats vector, none 64 bits wide."""
        from tools.zbaudit import load_budget
        from tools.zbaudit.passes import wave_io

        budget = load_budget()
        assert set(budget["boundary"]["wave_io"]) >= {"kernel.step"}
        result = audit(passes=["boundary", "op-census"], budget=budget)
        assert "boundary-wave-io" not in rules_of(result.findings)
        step = next(e for e in result.entries if e.name == "kernel.step")
        assert wave_io(step, resident_args=(0,)) == {
            "arrays_in": 2, "scalars_in": 1, "arrays_out": 3, "wide": [],
        }


class TestCollectiveVolume:
    @staticmethod
    def _psum_program():
        mesh = Mesh(np.asarray(jax.devices()), ("partitions",))
        return jax.shard_map(
            lambda x: jax.lax.psum(x, "partitions"),
            mesh=mesh, in_specs=P("partitions"), out_specs=P(),
        )

    def test_oversized_collective_fires(self):
        n = len(jax.devices())
        entry = audit_program(
            "fixture.bigcoll", self._psum_program(), f32(n, 256),
            collective=True,
        )
        budget = {"collective": {"per_round_budget_bytes": 1}}
        result = audit(
            passes=["collective-volume"], entries=[entry], budget=budget
        )
        assert "collective-volume" in rules_of(result.findings)

    def test_collective_in_noncollective_entry_fires(self):
        n = len(jax.devices())
        entry = audit_program(
            "fixture.sneaky", self._psum_program(), f32(n, 4),
            collective=False,
        )
        result = audit(
            passes=["collective-volume"], entries=[entry],
            budget={"collective": {"per_round_budget_bytes": 1 << 30}},
        )
        assert "collective-unexpected" in rules_of(result.findings)

    def test_under_budget_collective_is_quiet(self):
        n = len(jax.devices())
        entry = audit_program(
            "fixture.smallcoll", self._psum_program(), f32(n, 4),
            collective=True,
        )
        result = audit(
            passes=["collective-volume"], entries=[entry],
            budget={"collective": {"per_round_budget_bytes": 1 << 30}},
        )
        assert result.findings == []


class TestHbmBudget:
    SMALL = {
        "default_config": {
            "capacity": 64, "num_vars": 8, "sub_capacity": 8, "wave": 16,
        },
        "hbm": {"device_budget_bytes": 16, "capacity_table": [64]},
    }

    def test_oversized_entry_fires(self):
        entry = audit_program("fixture.fat", lambda x: x + 1.0, f32(1024))
        result = audit(
            passes=["hbm-budget"], entries=[entry], budget=self.SMALL
        )
        assert any(
            f.rule == "hbm-budget" and "fixture.fat" in f.message
            for f in result.findings
        )

    def test_within_budget_is_quiet(self):
        budget = {
            "default_config": self.SMALL["default_config"],
            "hbm": {"device_budget_bytes": 1 << 40, "capacity_table": [64]},
        }
        entry = audit_program("fixture.thin", lambda x: x + 1.0, f32(8))
        result = audit(passes=["hbm-budget"], entries=[entry], budget=budget)
        assert result.findings == []


class TestOpCensus:
    @staticmethod
    def _gather_entry():
        def lookup(table, idx):
            return table[idx]

        return audit_program(
            "kernel.step", lookup, f32(64),
            jax.ShapeDtypeStruct((8,), jnp.int32),
        )

    def test_over_budget_census_fires(self, tmp_path, monkeypatch):
        fake = tmp_path / "census_budget.json"
        fake.write_text(json.dumps({
            "backend": "cpu", "gather": 0, "scatter": 0,
            "gather_scatter_total": 0,
        }))
        # os.path.join(REPO_ROOT, <absolute>) resolves to the absolute path
        monkeypatch.setattr(passes_mod, "CENSUS_BUDGET_PATH", str(fake))
        result = audit(
            passes=["op-census"], entries=[self._gather_entry()], budget={}
        )
        assert "op-census" in rules_of(result.findings)

    def test_under_budget_emits_ratchet_hint(self, tmp_path, monkeypatch):
        fake = tmp_path / "census_budget.json"
        fake.write_text(json.dumps({
            "backend": "cpu", "gather": 1000, "scatter": 1000,
            "gather_scatter_total": 1000,
        }))
        monkeypatch.setattr(passes_mod, "CENSUS_BUDGET_PATH", str(fake))
        result = audit(
            passes=["op-census"], entries=[self._gather_entry()], budget={}
        )
        assert result.findings == []
        assert result.report["op-census"]["ratchet_hints"]

    def test_mismatched_backend_skips_gate(self, tmp_path, monkeypatch):
        fake = tmp_path / "census_budget.json"
        fake.write_text(json.dumps({"backend": "tpu", "gather": 0}))
        monkeypatch.setattr(passes_mod, "CENSUS_BUDGET_PATH", str(fake))
        result = audit(
            passes=["op-census"], entries=[self._gather_entry()], budget={}
        )
        assert result.findings == []
        assert "skipped" in result.report["op-census"]


class TestSignatureGuard:
    def test_cache_over_declared_max_fires(self):
        entry = audit_program(
            "fixture.churner", lambda x: x * 2.0, f32(4), max_signatures=1,
        )
        # compile two distinct signatures against a declared max of 1
        entry.entry.fn(jnp.zeros((4,), jnp.float32))
        entry.entry.fn(jnp.zeros((9,), jnp.float32))
        result = audit(
            passes=["signature-guard"], entries=[entry], budget={}
        )
        assert "signature-cache" in rules_of(result.findings)

    def test_cache_within_max_is_quiet(self):
        entry = audit_program(
            "fixture.stable", lambda x: x * 2.0, f32(4), max_signatures=2,
        )
        entry.entry.fn(jnp.zeros((4,), jnp.float32))
        result = audit(
            passes=["signature-guard"], entries=[entry], budget={}
        )
        assert result.findings == []


# -- baseline ratchet --------------------------------------------------------

class TestBaselineRatchet:
    def test_round_trip_and_ratchet(self, tmp_path):
        path = str(tmp_path / "baseline.json")
        f1 = Finding("hbm-budget", "zeebe_tpu/tpu/kernel.py", 10, "msg-a")
        f2 = Finding("dtype-i64", "zeebe_tpu/tpu/drive.py", 20, "msg-b")
        write_audit_baseline(path, [f1, f2])
        baseline = load_baseline(path)
        surfaced, baselined = apply_baseline([f1, f2], baseline)
        assert surfaced == [] and baselined == 2
        # a NEW finding is not grandfathered
        f3 = Finding("boundary-callback", "zeebe_tpu/tpu/shard.py", 5, "new")
        surfaced, baselined = apply_baseline([f1, f3], baseline)
        assert [f.rule for f in surfaced] == ["boundary-callback"]
        # ratchet down: rewrite after fixing f2 — f2 would now surface
        write_audit_baseline(path, [f1])
        surfaced, _ = apply_baseline([f1, f2], load_baseline(path))
        assert [f.rule for f in surfaced] == ["dtype-i64"]

    def test_baseline_comment_names_zbaudit(self, tmp_path):
        path = str(tmp_path / "baseline.json")
        write_audit_baseline(path, [])
        with open(path, encoding="utf-8") as f:
            doc = json.load(f)
        assert "zbaudit" in doc["comment"]
        assert doc["entries"] == {}

    def test_checked_in_baseline_is_empty(self):
        # the live tree audits clean: nothing is grandfathered
        path = os.path.join(REPO_ROOT, "tools", "zbaudit_baseline.json")
        with open(path, encoding="utf-8") as f:
            assert json.load(f)["entries"] == {}


# -- HBM model accuracy ------------------------------------------------------

class TestHbmModel:
    def test_model_matches_measured_device_bytes(self):
        """The closed-form state model vs real committed buffers: put the
        default-config state on device and sum buffer bytes (issue
        acceptance: within 10%; the model is an exact leaf-bytes sum, so
        this pins equality modulo backend padding)."""
        budget = load_budget()
        report = {}
        passes_mod.pass_hbm([], budget, report)
        model = report["hbm"]
        dc = budget["default_config"]
        state = state_mod.make_state(
            capacity=dc["capacity"], num_vars=dc["num_vars"],
            job_capacity=dc["capacity"], sub_capacity=dc["sub_capacity"],
        )
        measured = sum(
            jax.device_put(leaf).nbytes for leaf in jax.tree.leaves(state)
        )
        modeled = model["state_bytes_at_default_capacity"]
        assert abs(modeled - measured) / measured < 0.10

    def test_capacity_table_is_linear_in_capacity(self):
        budget = load_budget()
        report = {}
        passes_mod.pass_hbm([], budget, report)
        model = report["hbm"]
        slope = model["bytes_per_capacity_row"]
        fixed = model["fixed_bytes"]
        assert slope > 0
        for cap, total in model["capacity_table"].items():
            predicted = slope * int(cap) + fixed
            assert abs(predicted - total) / total < 0.01


# -- donation parity pins ----------------------------------------------------

def _timer_state(capacity=64, num_vars=8, due=3):
    """EngineState with ``due`` timers due at t<=10 (seeded directly,
    like test_job_backlog_probe seeds jobs)."""
    state = state_mod.make_state(
        capacity=capacity, num_vars=num_vars, job_capacity=capacity,
        sub_capacity=8,
    )
    timer_key = state_mod.host_i64(state.timer_key, 0).copy()
    timer_due = state_mod.host_i64(state.timer_due, 0).copy()
    for i in range(due):
        timer_key[i] = 100 + 7 * i
        timer_due[i] = 10
    return dataclasses.replace(
        state,
        timer_key=jnp.asarray(state_mod.host_planes(timer_key, column=True)),
        timer_due=jnp.asarray(state_mod.host_planes(timer_due, column=True)),
    )


class TestDonationParity:
    def test_tick_donated_matches_undonated(self):
        """kernel.tick donates its (read-only) state: the triggered batch
        must be bit-identical to the un-donated reference and the
        passthrough state bit-identical to the input."""
        state = _timer_state()
        now = jnp.asarray(100, jnp.int64)
        snapshot = [np.asarray(leaf) for leaf in jax.tree.leaves(state)]
        # un-donated reference first (it leaves `state` alive)
        ref_out, ref_count = kernel.tick_kernel(state, now)
        state2, out, count = kernel.tick_jit(state, now)
        assert int(count) == int(ref_count) == 3
        for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(ref_out)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(jax.tree.leaves(state2), snapshot):
            np.testing.assert_array_equal(np.asarray(a), b)

    def test_due_probe_donated_matches_undonated(self):
        eng = engine_mod.TpuPartitionEngine(capacity=64, sub_capacity=8)
        now = jnp.asarray(0, jnp.int64)
        ref = int(engine_mod._due_probe_kernel(eng.state, now))
        eng.state, mask = engine_mod._due_probe_jit(eng.state, now)
        assert int(mask) == ref
        # the rebound state is alive and probes identically again
        eng.state, mask2 = engine_mod._due_probe_jit(eng.state, now)
        assert int(mask2) == ref


# -- runtime recompile guard -------------------------------------------------

class TestRecompileGuard:
    def test_step_waves_of_varying_record_count_share_one_signature(self):
        """The serving-latency cliff zbaudit's signature guard exists
        for: waves carry a varying VALID count inside a fixed wave shape,
        so stepping different record counts must not recompile."""
        from zeebe_tpu.testing import graphs

        graph, _meta = graphs.build_graph()
        num_vars = max(graph.num_vars, 8)
        graph = dataclasses.replace(graph, num_vars=num_vars)
        state = state_mod.make_state(
            capacity=128, num_vars=num_vars, job_capacity=128,
            sub_capacity=8,
        )
        wave = rb.empty(16, num_vars)
        state, _em, _stats = kernel.step_jit(
            graph, state, wave, jnp.asarray(0, jnp.int64),
            synthetic_workers=False,
        )
        before = kernel.step_jit._cache_size()
        for count, now in ((1, 1000), (3, 2000)):
            wave = rb.empty(16, num_vars)
            wave = dataclasses.replace(
                wave,
                valid=wave.valid.at[:count].set(True),
                rtype=wave.rtype.at[:count].set(kernel.RT_CMD),
            )
            state, _em, _stats = kernel.step_jit(
                graph, state, wave, jnp.asarray(now, jnp.int64),
                synthetic_workers=False,
            )
        assert kernel.step_jit._cache_size() == before


# -- the gate itself ---------------------------------------------------------

class TestGate:
    def test_unknown_pass_rejected(self):
        with pytest.raises(ValueError, match="unknown zbaudit pass"):
            audit(passes=["no-such-pass"], entries=[], budget={})

    def test_budget_file_parses_with_required_sections(self):
        budget = load_budget()
        for section in ("default_config", "audit_config", "hbm", "dtype",
                        "collective"):
            assert section in budget

    def test_live_tree_audits_clean(self, tmp_path):
        """The CI invocation, in a clean subprocess (the in-process
        registry carries compile-cache state from other tests): exit 0,
        zero findings, every driver entry built."""
        out = str(tmp_path / "report.json")
        proc = subprocess.run(
            [sys.executable, "-m", "tools.zbaudit", "--json", "--out", out],
            cwd=REPO_ROOT, capture_output=True, text=True, timeout=600,
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
        )
        assert proc.returncode == 0, proc.stdout + proc.stderr
        with open(out, encoding="utf-8") as f:
            doc = json.load(f)
        assert doc["findings"] == []
        for name in ("kernel.step", "kernel.tick", "engine.due_probe",
                     "engine.credit_flush", "drive.round", "drive.quiesce", "shard.sharded_step",
                     "shard.frame_exchange", "shard.sharded_drive"):
            assert name in doc["entries"]
        assert doc["report"]["hbm"]["serving_peak_bytes"] > 0
