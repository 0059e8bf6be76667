"""Bench-trajectory regression sentinel.

Ingests every per-round bench artifact in the repo root — `BENCH_rNN.json`
(the config-1 device leg of bench.py), `BENCH_EARLY_rNN.json`
(the pre-suite early capture), `BENCH_SUITE_rNN.json` (the bench-suite
configs), `MULTICHIP_rNN.json` (the 8-device mesh dryrun, parsed from its
"dryrun_multichip OK" tail lines), `BENCH_STORM_rNN.json` (the config-18
open-loop read storm: per-leg saturation goodput + per-method p99),
`CHAOS_rNN.json` (the chaos conductor's
`--json` result: coverage + violation counts, never timings) — normalizes
each measured leg into a (config, metric, provenance) series across rounds,
and writes `BENCH_TRAJECTORY.json` with median + MAD noise bands per series.

Provenance is the point: a nodes/s number from a live TPU and the same
metric from the XLA-CPU stand-in are NOT one series, and averaging them
manufactures trends. Every point carries one of three tags, derived from
the artifact's host_mode flags and platform strings:

  real-device     measured against a live accelerator backend
  xla-cpu-standin device code path, but the backend was the XLA CPU
                  stand-in (device unreachable / cpu-backend run)
  host_mode       the chain's host-mode fallback path (no device code ran)

Unmeasured legs (value 0.0 with a device error — a hung device is not a
compute result) are excluded from series and listed under "skipped" so the
artifact still records that the round TRIED.

`--check` recomputes the trajectory and exits nonzero when the newest point
of any series is a noise-aware regression: at least MIN_POINTS rounds, the
latest value beyond max(3 * 1.4826 * MAD, 10% of |median|) from the rolling
baseline (median of the prior points) in the metric's bad direction, AND
the worst value the series has ever seen. Series whose baseline is itself
noise (relative MAD > 0.5 — the early device legs were) are
reported but never fail the check.

Stdlib-only on purpose: tools/lint.sh runs this everywhere, including
environments without the jax toolchain.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys
from typing import Dict, List, Optional, Tuple

SCHEMA = "bench-trajectory/v1"
OUTPUT = "BENCH_TRAJECTORY.json"
MIN_POINTS = 3          # fewer rounds -> status "short", never checked
REL_BAND_FLOOR = 0.10   # band is never tighter than 10% of |median|
MAD_SIGMA = 1.4826      # MAD -> sigma for a normal distribution
NOISY_REL_MAD = 0.5     # baseline noisier than this -> status "noisy"

_ROUND_RE = re.compile(r"_r(\d+)\.json$")

# -------------------------------------------------------------- provenance


def _provenance(platform: Optional[str], host_mode) -> str:
    """Map an artifact's platform string + host_mode flag to a leg tag."""
    if host_mode:
        return "host_mode"
    p = (platform or "").lower()
    if "wedged" in p or "cpu-backend" in p or "standin" in p:
        return "xla-cpu-standin"
    if "live" in p or "tpu" in p:
        return "real-device"
    # no platform recorded (the single-leg BENCH_rNN artifacts): bench.py
    # lands a value only from its device leg, so it is a device number
    return "real-device"


def _direction(metric: str, unit: Optional[str]) -> Optional[str]:
    """"higher" / "lower" is better, None when the metric is unjudgeable."""
    u = (unit or "").lower()
    m = metric.lower()
    if "modeled" in m:
        # an analytic model, not a measurement: the sentinel reports it
        # but never gates on it (the provenance-split contract)
        return None
    if "overhead" in m:
        # config-21 profiler-overhead A/B: the <=2% gate lives in the
        # bench itself where the legs run back-to-back; cross-round
        # wall-clock noise on the shared box swamps a sub-2% effect,
        # so the sentinel reports the series without gating
        return None
    if "per_sec" in m or "/s" in u:
        return "higher"
    if m.endswith(("_s", "_ms", "_seconds")) or u in ("s", "ms", "seconds"):
        return "lower"
    if u.startswith("b/") or u in ("bytes", "mb") or "bytes_per" in m:
        # wire/disk footprint series (config-20 bytes-per-commit A/B):
        # fewer bytes moved is the win
        return "lower"
    return None


# -------------------------------------------------------------- ingestion

# "dryrun_multichip OK" tail lines -> (metric, value) extractors. Counts,
# not rates: the dryrun proves parity at scale, so the series track its
# COVERAGE (lanes swept, nodes/segments planned, churn rounds survived);
# direction is unjudgeable, the sentinel reports them without gating.
_MULTICHIP_PATTERNS: Tuple[Tuple[str, "re.Pattern"], ...] = (
    ("multichip_checksum_lanes",
     re.compile(r"OK: (\d+) lanes over \d+ devices")),
    # old ("commit of N nodes") and new ("commit — N nodes") tail formats
    ("multichip_planned_nodes",
     re.compile(r"sharded planned commit (?:of|—) (\d+) nodes")),
    ("multichip_planned_segments", re.compile(r"(\d+) segments")),
    ("multichip_resident_churn_rounds", re.compile(r"(\d+) churn rounds")),
)


def _multichip_points(data: dict, rnd: int,
                      source: str) -> Tuple[List[dict], List[dict]]:
    """One MULTICHIP_rNN.json -> ([points], [skipped]). The dryrun runs
    on the forced-host virtual mesh (no multi-chip device), so every
    point is provenance-tagged xla-cpu-standin; a wedged round (rc != 0)
    records that it TRIED, exactly like an unmeasured bench leg."""
    config = f"multichip-{data.get('n_devices', '?')}dev"
    if not data.get("ok") or data.get("rc"):
        return [], [{
            "round": rnd, "source": source, "config": config,
            "metric": "multichip_dryrun",
            "reason": f"dryrun wedged (rc={data.get('rc')})",
        }]
    points: List[dict] = []
    tail = data.get("tail") or ""
    for metric, pat in _MULTICHIP_PATTERNS:
        m = pat.search(tail)
        if m:
            points.append({
                "round": rnd, "source": source, "config": config,
                "metric": metric, "value": float(m.group(1)),
                "unit": None, "vs_baseline": None,
                "provenance": "xla-cpu-standin",
            })
    return points, []


def _chaos_points(data: dict, rnd: int,
                  source: str) -> Tuple[List[dict], List[dict]]:
    """One CHAOS_rNN.json (the conductor's --json result) -> coverage
    series. Counts, not rates — the conductor proves invariants hold
    under injected faults, so the series track how much of the fault
    matrix each round exercised (failpoints fired, subsystems touched,
    blocks survived) plus the violation count itself; direction is
    unjudgeable, the sentinel reports them without gating. A run that
    recorded violations still ingests — a rising violations series in
    the artifact history is exactly what the sentinel is for."""
    config = f"chaos-seed{data.get('seed', '?')}"
    cov = data.get("coverage") or {}
    final = data.get("final") or {}
    metrics = (
        ("chaos_steps", data.get("steps")),
        ("chaos_violations", len(data.get("violations") or [])),
        ("chaos_failpoints_fired", cov.get("failpoints_fired")),
        ("chaos_subsystems", len(cov.get("subsystems") or [])),
        ("chaos_height", final.get("height")),
    )
    points: List[dict] = []
    for metric, value in metrics:
        if isinstance(value, (int, float)):
            points.append({
                "round": rnd, "source": source, "config": config,
                "metric": metric, "value": float(value),
                "unit": None, "vs_baseline": None,
                "provenance": "xla-cpu-standin",
            })
    return points, []


def _storm_points(data: dict, rnd: int,
                  source: str) -> Tuple[List[dict], List[dict]]:
    """One BENCH_STORM_rNN.json (the config-18 open-loop read storm) ->
    per-leg series: saturation goodput (higher-better via per_sec) and
    per-method p99 at the saturated rung (lower-better via _ms). Both
    the locked foil and the view leg ingest — the A/B ratio regressing
    is exactly a lock-discipline leak the sentinel should catch. A
    smoke-mode artifact is a liveness probe, not a measurement: its
    rungs are too short for stable percentiles, so it is recorded as
    skipped rather than polluting the series."""
    config = data.get("config", 18)
    if data.get("smoke"):
        return [], [{
            "round": rnd, "source": source, "config": config,
            "metric": "storm", "reason": "smoke artifact (unmeasured)",
        }]
    points: List[dict] = []
    prov = _provenance(data.get("platform"), data.get("host_mode"))
    for leg_name, leg in sorted((data.get("legs") or {}).items()):
        sat = leg.get("saturation_per_sec")
        if isinstance(sat, (int, float)) and sat > 0:
            points.append({
                "round": rnd, "source": source, "config": config,
                "metric": f"storm_{leg_name}_saturation_per_sec",
                "value": float(sat), "unit": "req/s",
                "vs_baseline": data.get("view_vs_locked_saturation"),
                "provenance": prov,
            })
        for method, pcts in sorted((leg.get("methods") or {}).items()):
            p99 = pcts.get("p99_ms")
            if isinstance(p99, (int, float)) and p99 > 0:
                points.append({
                    "round": rnd, "source": source, "config": config,
                    "metric": f"storm_{leg_name}_{method}_p99_ms",
                    "value": float(p99), "unit": "ms",
                    "vs_baseline": None, "provenance": prov,
                })
    return points, []


def _round_of(path: str) -> Optional[int]:
    m = _ROUND_RE.search(os.path.basename(path))
    return int(m.group(1)) if m else None


def _entry_points(entry: dict, rnd: int, source: str,
                  platform: Optional[str], config,
                  host_mode) -> Tuple[List[dict], List[dict]]:
    """One result dict -> ([points], [skipped]). A point is a measured
    value of a named metric; everything else is context."""
    metric = entry.get("metric")
    if not metric:
        return [], []
    value = entry.get("value")
    error = entry.get("error")
    if not isinstance(value, (int, float)) or (value == 0.0 and error) or \
            (value == 0.0 and not error):
        # a zero with an error string is a hung device, not a measurement;
        # a bare zero is equally unmeasured (the bench never emits true 0)
        return [], [{
            "round": rnd, "source": source, "config": config,
            "metric": metric,
            "reason": error or "unmeasured (value 0.0)",
        }]
    prov = _provenance(platform, entry.get("host_mode", host_mode))
    return [{
        "round": rnd, "source": source, "config": config, "metric": metric,
        "value": float(value), "unit": entry.get("unit"),
        "vs_baseline": entry.get("vs_baseline"), "provenance": prov,
    }], []


def load_artifacts(root: str) -> Tuple[List[dict], List[dict]]:
    """Scan [root] for round artifacts; returns (points, skipped). The
    MULTICHIP_PALLAS_* numeric-parity dumps and this module's own output
    stay out of scope (raw digest words / derived data respectively)."""
    points: List[dict] = []
    skipped: List[dict] = []
    paths = sorted(glob.glob(os.path.join(root, "BENCH_*.json")))
    paths += sorted(p for p in glob.glob(
        os.path.join(root, "MULTICHIP_*.json"))
        if not os.path.basename(p).startswith("MULTICHIP_PALLAS"))
    paths += sorted(glob.glob(os.path.join(root, "CHAOS_*.json")))
    for path in paths:
        name = os.path.basename(path)
        if name == OUTPUT:
            continue
        rnd = _round_of(path)
        if rnd is None:
            continue
        try:
            with open(path) as f:
                data = json.load(f)
        except (OSError, ValueError) as e:
            skipped.append({"round": None, "source": name,
                            "reason": f"unreadable artifact: {e}"})
            continue
        if name.startswith("MULTICHIP_"):
            p, s = _multichip_points(data, rnd, name)
            points += p
            skipped += s
        elif name.startswith("CHAOS_"):
            p, s = _chaos_points(data, rnd, name)
            points += p
            skipped += s
        elif name.startswith("BENCH_STORM_"):
            p, s = _storm_points(data, rnd, name)
            points += p
            skipped += s
        elif name.startswith("BENCH_SUITE_"):
            platform = data.get("platform")
            results = data.get("results") or []
            # a metric-less companion dict (config 10's cold/host_mode
            # context line) can carry the config's host_mode flag
            host_by_config: Dict[object, object] = {}
            for r in results:
                if "config" in r and r.get("host_mode") is not None:
                    host_by_config[r["config"]] = r["host_mode"]
            for r in results:
                cfg = r.get("config")
                p, s = _entry_points(r, rnd, name, platform, cfg,
                                     host_by_config.get(cfg))
                points += p
                skipped += s
        elif name.startswith("BENCH_EARLY_"):
            p, s = _entry_points(data, rnd, name, data.get("platform"),
                                 "early", data.get("host_mode"))
            points += p
            skipped += s
        else:  # BENCH_rNN: single device leg wrapped in {n, cmd, rc, tail,
            #  parsed}
            entry = data.get("parsed") if isinstance(
                data.get("parsed"), dict) else data
            p, s = _entry_points(entry, rnd, name, entry.get("platform"),
                                 "device-leg", entry.get("host_mode"))
            points += p
            skipped += s
    return points, skipped


# -------------------------------------------------------------- statistics


def _median(xs: List[float]) -> float:
    s = sorted(xs)
    n = len(s)
    mid = n // 2
    return s[mid] if n % 2 else (s[mid - 1] + s[mid]) / 2.0


def _mad(xs: List[float], med: float) -> float:
    return _median([abs(x - med) for x in xs])


def _series_key(config, metric: str, provenance: str) -> str:
    return f"cfg={config}|{metric}|{provenance}"


def build_trajectory(points: List[dict], skipped: List[dict]) -> dict:
    """Group points into per-(config, metric, provenance) series and judge
    each one's newest point against its rolling baseline."""
    series: Dict[str, dict] = {}
    for pt in points:
        key = _series_key(pt["config"], pt["metric"], pt["provenance"])
        s = series.setdefault(key, {
            "config": pt["config"], "metric": pt["metric"],
            "provenance": pt["provenance"], "unit": pt["unit"],
            "points": [],
        })
        s["points"].append({"round": pt["round"], "value": pt["value"],
                            "source": pt["source"]})

    regressions: List[dict] = []
    for key in sorted(series):
        s = series[key]
        s["points"].sort(key=lambda p: (p["round"], p["source"]))
        values = [p["value"] for p in s["points"]]
        direction = _direction(s["metric"], s.get("unit"))
        s["direction"] = direction
        s["n"] = len(values)
        med = _median(values)
        mad = _mad(values, med)
        s["median"] = round(med, 4)
        s["mad"] = round(mad, 4)
        if len(values) < MIN_POINTS:
            s["status"] = "short"
            continue
        if direction is None:
            s["status"] = "unjudged"
            continue
        latest = values[-1]
        prior = values[:-1]
        baseline = _median(prior)
        prior_mad = _mad(prior, baseline)
        band = max(MAD_SIGMA * 3.0 * prior_mad,
                   REL_BAND_FLOOR * abs(baseline))
        s["baseline"] = round(baseline, 4)
        s["band"] = round(band, 4)
        if baseline and prior_mad / abs(baseline) > NOISY_REL_MAD:
            # the early device series swing harder than any signal;
            # report them, never gate on them
            s["status"] = "noisy"
            continue
        if direction == "higher":
            regressed = latest < baseline - band and latest == min(values)
        else:
            regressed = latest > baseline + band and latest == max(values)
        if regressed:
            s["status"] = "regression"
            regressions.append({
                "series": key, "latest": latest,
                "baseline": round(baseline, 4), "band": round(band, 4),
                "round": s["points"][-1]["round"],
                "source": s["points"][-1]["source"],
            })
        else:
            s["status"] = "ok"

    return {
        "schema": SCHEMA,
        "rounds": sorted({pt["round"] for pt in points}),
        "series": series,
        "regressions": regressions,
        "skipped": sorted(
            skipped, key=lambda s: (s.get("round") or 0, s["source"],
                                    s.get("metric") or "")),
    }


# -------------------------------------------------------------- CLI


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m coreth_tpu.bench.trajectory",
        description="Normalize BENCH_* round artifacts into "
                    f"{OUTPUT} and flag noise-aware regressions.")
    ap.add_argument("--root", default=".",
                    help="directory holding the BENCH_* artifacts "
                         "(default: cwd)")
    ap.add_argument("--out", default=None,
                    help=f"output path (default: <root>/{OUTPUT})")
    ap.add_argument("--check", action="store_true",
                    help="exit 1 when the newest round regresses any "
                         "series beyond its noise band")
    args = ap.parse_args(argv)

    points, skipped = load_artifacts(args.root)
    if not points and not skipped:
        # a fresh checkout has no artifacts; the lint stage must not fail
        print("bench.trajectory: no BENCH_* artifacts under "
              f"{args.root!r}; nothing to check")
        return 0

    out = build_trajectory(points, skipped)
    out_path = args.out or os.path.join(args.root, OUTPUT)
    with open(out_path, "w") as f:
        json.dump(out, f, indent=1, sort_keys=True)
        f.write("\n")

    n_checked = sum(1 for s in out["series"].values()
                    if s["status"] in ("ok", "regression"))
    print(f"bench.trajectory: {len(out['series'])} series over rounds "
          f"{out['rounds']} ({n_checked} gated, "
          f"{len(out['skipped'])} unmeasured legs) -> {out_path}")
    for r in out["regressions"]:
        print(f"REGRESSION {r['series']}: latest {r['latest']} vs baseline "
              f"{r['baseline']} (band {r['band']}) in {r['source']}")
    if args.check and out["regressions"]:
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
