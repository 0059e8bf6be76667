"""Whole runs of tiny cells on the CPU: the harness's look for a chip is
skipped and everything after it runs as on the chip: set-up, the warm-up
block, a one-block window, the reference, the checks and the result."""

import io
import json

import pytest

import benchtools
from benchmark import control, harness


def run(root, name, seed=11, trace=False):
    out, err = io.StringIO(), io.StringIO()
    res = harness.run_cell(name, seed, 0.0, trace, root=root,
                           require_tpu=False, out=out, err=err)
    return res, out.getvalue(), err.getvalue()


@pytest.mark.parametrize("seed", [2 ** 31 + 7, 3])
def test_a_sound_run_is_correct(tmp_path, seed):
    root = benchtools.tiny_root(tmp_path)
    res, out, err = run(root, "tiny-transfers", seed=seed)
    assert res["correct"], err
    assert res["attempted"] == 1 and res["failed"] == 0
    assert set(res["metrics"]) == {"mgas_per_s", "block_p95_ms", "setup_s"}
    # the last line is the result; the checks come last in it, and are
    # the last lines of standard error too
    last = json.loads(out.strip().splitlines()[-1])
    assert list(last)[-1] == "checks"
    assert out.strip().splitlines()[-2].startswith("supply ")
    tail = err.strip().splitlines()[-len(res["checks"]):]
    assert all(line.startswith("check ") for line in tail)
    assert res["checks"]["state_root_diffs"]["value"] == 0
    assert res["checks"]["window_device_segments"]["value"] >= 1


def test_a_traced_run_reports_per_layer_metrics_and_a_new_reader(tmp_path):
    root = benchtools.tiny_root(tmp_path)
    with open(f"{root}/benchmark/metrics/extra.blocks_in_window.py",
              "w") as f:
        f.write("def read(run):\n    return float(run.n_blocks)\n")
    with open(f"{root}/BENCHMARK.json") as f:
        bench = json.load(f)
    bench["per_layer"].append({"name": "extra.blocks_in_window", "unit": "1",
                               "better": "higher", "source": "host_clock",
                               "layer": "VM", "moves": "mgas_per_s",
                               "workloads": ["tiny-transfers"]})
    benchtools.write(root, "BENCHMARK.json", bench)
    res, _, err = run(root, "tiny-transfers", trace=True)
    assert res["correct"], err
    m = res["metrics"]
    assert m["extra.blocks_in_window"]["value"] == 1.0
    assert m["executor.cache_miss_share"]["value"] == 100.0
    assert m["commit.compile_ms"]["value"] > 0
    assert "mgas_per_s" not in m
    # the CPU has no device plane: no device metric is written from it
    assert "device.idle_share" not in m
    assert "kernel.commit_program_roofline" not in m
    assert res["device"]["window_s"] > 0 and "breakdown" in res


def test_the_control_fails_where_the_program_passes(tmp_path, capsys):
    root = benchtools.tiny_root(tmp_path)
    assert control.main(["--workload", "tiny-transfers", "--seeds", "3,4,5",
                         "--seconds", "0"], root=root,
                        require_tpu=False) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    seeds = [json.loads(l.split(" ", 1)[1]) for l in lines
             if l.startswith("seed ")]
    assert [s["seed"] for s in seeds] == [3, 4, 5]
    for s in seeds:
        assert s["program_correct"]
        assert s["control_fails"] == ["state_root_diffs"]
    summary = json.loads(lines[-1].split(" ", 1)[1])
    assert summary["program_correct_on_every_seed"]
    assert summary["control_failed_on_every_seed"]
