"""Bench-trajectory sentinel (ISSUE 12): artifact ingestion across the
three artifact shapes, provenance tagging, noise-aware regression
detection, and the CLI contract tools/lint.sh relies on (clean skip on an
artifact-less checkout, nonzero exit on a regression)."""

import json
import os

from coreth_tpu.bench.trajectory import (OUTPUT, build_trajectory,
                                         load_artifacts, main)


def _suite(tmp_path, rnd, value, platform="cpu-backend (device unreachable)",
           config=3, metric="block_insert_1k_txs_per_sec", unit="txs/s",
           extra=None):
    results = [{"config": config, "metric": metric, "value": value,
                "unit": unit, "vs_baseline": 1.0}]
    if extra:
        results += extra
    (tmp_path / f"BENCH_SUITE_r{rnd:02d}.json").write_text(json.dumps(
        {"round": rnd, "platform": platform, "results": results}))


def _series(out):
    return out["series"]


class TestIngestion:
    def test_three_artifact_shapes_normalize(self, tmp_path):
        _suite(tmp_path, 1, 1000.0)
        (tmp_path / "BENCH_r01.json").write_text(json.dumps({
            "n": 1, "cmd": "python bench.py", "rc": 0, "tail": "",
            "parsed": {"metric": "trie_commit_nodes_per_sec",
                       "value": 32000.0, "unit": "nodes/s",
                       "vs_baseline": 0.4}}))
        (tmp_path / "BENCH_EARLY_r01.json").write_text(json.dumps({
            "metric": "trie_commit_nodes_per_sec", "value": 59000.0,
            "unit": "nodes/s", "platform": "TPU v5 lite (live)",
            "mode": "early"}))
        points, skipped = load_artifacts(str(tmp_path))
        assert len(points) == 3 and skipped == []
        out = build_trajectory(points, skipped)
        assert set(_series(out)) == {
            "cfg=3|block_insert_1k_txs_per_sec|xla-cpu-standin",
            "cfg=device-leg|trie_commit_nodes_per_sec|real-device",
            "cfg=early|trie_commit_nodes_per_sec|real-device",
        }

    def test_provenance_tags(self, tmp_path):
        # host_mode flag (even from a metric-less companion dict) beats
        # the platform string; "live" platforms are real-device
        _suite(tmp_path, 1, 200.0, platform="TPU v5 (live)", config=10,
               metric="resident_block_insert_txs_per_sec",
               extra=[{"config": 10, "host_mode": True,
                       "cold_txs_per_sec": 190.0}])
        points, _ = load_artifacts(str(tmp_path))
        assert points[0]["provenance"] == "host_mode"

    def test_config19_shard_sweep_ingests_with_honest_provenance(
            self, tmp_path):
        # the exec-shard A/B is a CPU-process bench: its companion line
        # stamps host_mode + cores, so the series is tagged host_mode and
        # the noise gate never mistakes a 1-core ~1.0x round for a
        # device-leg regression
        for rnd in (1, 2, 3):
            _suite(tmp_path, rnd, 1200.0 + rnd, config=19,
                   metric="sharded_block_insert_txs_per_sec",
                   extra=[{"config": 19, "host_mode": True, "cores": 1,
                           "serial_txs_per_sec": 1100.0,
                           "shards": {"4": {"ratio_vs_serial": 1.01}}}])
        points, skipped = load_artifacts(str(tmp_path))
        cfg19 = [p for p in points if p["config"] == 19]
        assert len(cfg19) == 3 and skipped == []
        assert all(p["provenance"] == "host_mode" for p in cfg19)
        out = build_trajectory(points, skipped)
        key = "cfg=19|sharded_block_insert_txs_per_sec|host_mode"
        assert out["series"][key]["n"] == 3

    def test_unmeasured_device_leg_is_skipped_not_a_point(self, tmp_path):
        (tmp_path / "BENCH_r02.json").write_text(json.dumps({
            "n": 2, "cmd": "python bench.py", "rc": 0, "tail": "",
            "parsed": {"metric": "trie_commit_nodes_per_sec", "value": 0.0,
                       "unit": "nodes/s", "vs_baseline": 0.0,
                       "error": "device wedged: no progress"}}))
        points, skipped = load_artifacts(str(tmp_path))
        assert points == []
        assert len(skipped) == 1
        assert "wedged" in skipped[0]["reason"]

    def test_own_output_out_of_scope(self, tmp_path):
        (tmp_path / OUTPUT).write_text('{"schema": "stale"}')
        points, skipped = load_artifacts(str(tmp_path))
        assert points == [] and skipped == []

    def test_multichip_ok_round_parses_coverage_series(self, tmp_path):
        # the r04+ tail shape: checksum sweep + sharded planned commit
        # (new "— N nodes" wording) + resident churn line
        (tmp_path / "MULTICHIP_r04.json").write_text(json.dumps({
            "round": 4, "ok": True, "rc": 0, "n_devices": 8,
            "tail": "dryrun_multichip OK: 1024 lanes over 8 devices\n"
                    "sharded planned commit — 26862 nodes, 17 segments\n"
                    "RESIDENT executor sharded over 8 devices — 3 churn "
                    "rounds + rollback bit-exact vs host oracle"}))
        points, skipped = load_artifacts(str(tmp_path))
        assert skipped == []
        got = {p["metric"]: p["value"] for p in points}
        assert got == {"multichip_checksum_lanes": 1024.0,
                       "multichip_planned_nodes": 26862.0,
                       "multichip_planned_segments": 17.0,
                       "multichip_resident_churn_rounds": 3.0}
        assert all(p["provenance"] == "xla-cpu-standin" for p in points)
        assert all(p["config"] == "multichip-8dev" for p in points)
        # counts have no judgeable direction: reported, never gated
        out = build_trajectory(points, [])
        assert out["regressions"] == []
        for s in out["series"].values():
            assert s["status"] in ("short", "unjudged")

    def test_multichip_old_tail_format_still_parses(self, tmp_path):
        # the r02-era wording ("commit of N nodes")
        (tmp_path / "MULTICHIP_r02.json").write_text(json.dumps({
            "round": 2, "ok": True, "rc": 0, "n_devices": 8,
            "tail": "sharded planned commit of 412 nodes matches the "
                    "host oracle root"}))
        points, _ = load_artifacts(str(tmp_path))
        assert {p["metric"] for p in points} == {"multichip_planned_nodes"}
        assert points[0]["value"] == 412.0

    def test_multichip_wedged_round_is_skipped_not_a_point(self, tmp_path):
        (tmp_path / "MULTICHIP_r01.json").write_text(json.dumps({
            "round": 1, "ok": False, "rc": 124, "n_devices": 8,
            "tail": ""}))
        points, skipped = load_artifacts(str(tmp_path))
        assert points == []
        assert len(skipped) == 1
        assert skipped[0]["reason"] == "dryrun wedged (rc=124)"

    def test_multichip_pallas_dumps_stay_out_of_scope(self, tmp_path):
        # numeric-parity dumps share the prefix but aren't dryrun rounds
        (tmp_path / "MULTICHIP_PALLAS_r03.json").write_text('{"raw": 1}')
        points, skipped = load_artifacts(str(tmp_path))
        assert points == [] and skipped == []


def _storm(tmp_path, rnd, view_sat=3400.0, locked_sat=2900.0,
           view_p99=300.0, smoke=False):
    legs = {
        "locked": {"saturation_per_sec": locked_sat,
                   "methods": {"eth_getBalance": {
                       "count": 100, "p50_ms": 280.0, "p90_ms": 530.0,
                       "p99_ms": 570.0}}},
        "view": {"saturation_per_sec": view_sat,
                 "methods": {"eth_getBalance": {
                     "count": 100, "p50_ms": 240.0, "p90_ms": 290.0,
                     "p99_ms": view_p99}}},
    }
    (tmp_path / f"BENCH_STORM_r{rnd:02d}.json").write_text(json.dumps({
        "schema": "bench-storm/v1", "config": 18, "platform": "cpu",
        "host_mode": True, "smoke": smoke, "legs": legs,
        "view_vs_locked_saturation": round(view_sat / locked_sat, 3)}))


class TestStormIngestion:
    def test_storm_artifact_yields_per_leg_series(self, tmp_path):
        _storm(tmp_path, 13)
        points, skipped = load_artifacts(str(tmp_path))
        assert skipped == []
        by_metric = {p["metric"]: p for p in points}
        assert set(by_metric) == {
            "storm_locked_saturation_per_sec",
            "storm_locked_eth_getBalance_p99_ms",
            "storm_view_saturation_per_sec",
            "storm_view_eth_getBalance_p99_ms",
        }
        # a host-concurrency bench: no device code ran
        assert all(p["provenance"] == "host_mode" for p in points)
        assert all(p["config"] == 18 for p in points)
        assert by_metric["storm_view_saturation_per_sec"][
            "vs_baseline"] == 1.172
        out = build_trajectory(points, skipped)
        sat = out["series"]["cfg=18|storm_view_saturation_per_sec|host_mode"]
        p99 = out["series"][
            "cfg=18|storm_view_eth_getBalance_p99_ms|host_mode"]
        assert sat["direction"] == "higher"   # goodput: more is better
        assert p99["direction"] == "lower"    # tail latency: less is better

    def test_smoke_storm_is_skipped_not_a_point(self, tmp_path):
        _storm(tmp_path, 14, smoke=True)
        points, skipped = load_artifacts(str(tmp_path))
        assert points == []
        assert len(skipped) == 1
        assert "smoke" in skipped[0]["reason"]

    def test_p99_blowup_fails_check(self, tmp_path):
        # noise-aware gate on the storm series: p99 is lower-is-better,
        # so a 2x tail-latency blowup in the newest round must trip it
        for rnd, p99 in ((1, 300.0), (2, 310.0), (3, 295.0), (4, 640.0)):
            _storm(tmp_path, rnd, view_p99=p99)
        assert main(["--check", "--root", str(tmp_path)]) == 1
        out = json.loads((tmp_path / OUTPUT).read_text())
        assert any("storm_view_eth_getBalance_p99_ms" in r["series"]
                   for r in out["regressions"])

    def test_saturation_collapse_fails_check(self, tmp_path):
        for rnd, sat in ((1, 3400.0), (2, 3450.0), (3, 3380.0), (4, 2100.0)):
            _storm(tmp_path, rnd, view_sat=sat)
        assert main(["--check", "--root", str(tmp_path)]) == 1

    def test_stable_storm_rounds_pass(self, tmp_path):
        for rnd, sat in ((1, 3400.0), (2, 3450.0), (3, 3380.0), (4, 3420.0)):
            _storm(tmp_path, rnd, view_sat=sat)
        assert main(["--check", "--root", str(tmp_path)]) == 0


class TestRegressionGate:
    def test_twenty_percent_regression_fails_check(self, tmp_path, capsys):
        for rnd, v in ((1, 1000.0), (2, 1010.0), (3, 995.0), (4, 800.0)):
            _suite(tmp_path, rnd, v)
        rc = main(["--check", "--root", str(tmp_path)])
        assert rc == 1
        assert "REGRESSION" in capsys.readouterr().out
        out = json.loads((tmp_path / OUTPUT).read_text())
        assert len(out["regressions"]) == 1
        key = out["regressions"][0]["series"]
        assert out["series"][key]["status"] == "regression"

    def test_stable_series_passes(self, tmp_path):
        for rnd, v in ((1, 1000.0), (2, 1010.0), (3, 995.0), (4, 1005.0)):
            _suite(tmp_path, rnd, v)
        assert main(["--check", "--root", str(tmp_path)]) == 0

    def test_in_band_dip_is_not_a_regression(self, tmp_path):
        # 8% down is inside the 10% relative floor
        for rnd, v in ((1, 1000.0), (2, 1010.0), (3, 995.0), (4, 920.0)):
            _suite(tmp_path, rnd, v)
        assert main(["--check", "--root", str(tmp_path)]) == 0

    def test_noisy_series_never_gates(self, tmp_path):
        # early-device swings: relative MAD way past 0.5 -> reported, not gated
        for rnd, v in ((1, 100.0), (2, 1700.0), (3, 300.0), (4, 40.0)):
            _suite(tmp_path, rnd, v)
        assert main(["--check", "--root", str(tmp_path)]) == 0
        out = json.loads((tmp_path / OUTPUT).read_text())
        assert list(out["series"].values())[0]["status"] == "noisy"

    def test_lower_is_better_direction(self, tmp_path):
        for rnd, v in ((1, 1.0), (2, 1.02), (3, 0.99), (4, 1.5)):
            _suite(tmp_path, rnd, v, metric="chain_insert_latency_s",
                   unit="s")
        rc = main(["--check", "--root", str(tmp_path)])
        assert rc == 1

    def test_short_series_unchecked(self, tmp_path):
        for rnd, v in ((1, 1000.0), (2, 500.0)):
            _suite(tmp_path, rnd, v)
        assert main(["--check", "--root", str(tmp_path)]) == 0
        out = json.loads((tmp_path / OUTPUT).read_text())
        assert list(out["series"].values())[0]["status"] == "short"


class TestConfig20Ingestion:
    """Config-20 bytes-per-commit envelope (PR 18): measured wire/h2d
    series gate lower-is-better; the planned column is a MODEL and is
    reported without gating."""

    def test_wire_bytes_per_leaf_direction_and_provenance(self, tmp_path):
        _suite(tmp_path, 1, 80.0, config=20,
               metric="lean_row_wire_bytes_per_leaf", unit="B/leaf",
               platform="xla-cpu-standin (no device leg)")
        points, skipped = load_artifacts(str(tmp_path))
        assert skipped == []
        assert points[0]["provenance"] == "xla-cpu-standin"
        out = build_trajectory(points, [])
        s = out["series"][
            "cfg=20|lean_row_wire_bytes_per_leaf|xla-cpu-standin"]
        assert s["direction"] == "lower"

    def test_h2d_bytes_blowup_fails_check(self, tmp_path):
        # the lean leg quietly shipping full rows again (2x the bytes)
        # is exactly the regression the sentinel must trip on
        for rnd, v in ((1, 67000.0), (2, 66500.0), (3, 67400.0),
                       (4, 140000.0)):
            _suite(tmp_path, rnd, v, config=20,
                   metric="lean_h2d_bytes_per_commit", unit="B/commit")
        assert main(["--check", "--root", str(tmp_path)]) == 1

    def test_modeled_series_reported_never_gated(self, tmp_path):
        # same blowup shape, but the metric is a model: unjudged, rc 0
        for rnd, v in ((1, 250000.0), (2, 255000.0), (3, 249000.0),
                       (4, 900000.0)):
            _suite(tmp_path, rnd, v, config=20,
                   metric="planned_modeled_bytes_per_commit",
                   unit="B/commit")
        assert main(["--check", "--root", str(tmp_path)]) == 0
        out = json.loads((tmp_path / OUTPUT).read_text())
        s = out["series"][
            "cfg=20|planned_modeled_bytes_per_commit|xla-cpu-standin"]
        assert s["status"] in ("short", "unjudged")


class TestCLIContract:
    def test_empty_checkout_skips_cleanly(self, tmp_path, capsys):
        assert main(["--check", "--root", str(tmp_path)]) == 0
        assert "nothing to check" in capsys.readouterr().out
        assert not (tmp_path / OUTPUT).exists()

    def test_output_is_deterministic(self, tmp_path):
        for rnd, v in ((1, 1000.0), (2, 1010.0), (3, 995.0)):
            _suite(tmp_path, rnd, v)
        assert main(["--root", str(tmp_path)]) == 0
        first = (tmp_path / OUTPUT).read_text()
        assert main(["--root", str(tmp_path)]) == 0
        assert (tmp_path / OUTPUT).read_text() == first

    def test_real_repo_artifacts_pass(self):
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        if not any(f.startswith("BENCH_") and f != OUTPUT
                   for f in os.listdir(repo)):
            return  # artifact-less checkout: nothing to assert
        points, _ = load_artifacts(repo)
        out = build_trajectory(points, [])
        assert out["regressions"] == []
        # every device leg carries a provenance tag
        assert all(s["provenance"] in
                   ("real-device", "xla-cpu-standin", "host_mode")
                   for s in out["series"].values())
