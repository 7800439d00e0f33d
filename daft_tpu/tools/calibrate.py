"""Cost-model calibration report: observed-vs-predicted -> cost overrides.

``python -m daft_tpu.tools.calibrate`` (``make calibrate-report``) replays the
placement ledger's observed-vs-predicted samples (observability/placement.py —
each dispatched device stage's per-term span timings next to the
CostBreakdown the decision priced) into suggested cost-model env
override values (DAFT_TPU_COST_RTT and friends) — the tool the ROADMAP's star-join recalibration item needs:
run a representative workload on the real silicon, read the report, export
the suggested overrides, and the auto tier stops guessing.

Modes:
- no args: run a small built-in probe workload (grouped + ungrouped agg and a
  device UDF shape) under ``device_mode=on`` with
  ``DAFT_TPU_PLACEMENT_PRICE_FORCED=1``, so every forced dispatch carries a
  priced breakdown AND an observation — works on any backend, including the
  CPU CI one.
- ``--ledger FILE.json``: read records previously dumped with
  ``daft_tpu.observability.placement.ledger().snapshot()`` instead of
  running the probe workload.
- ``--json``: machine-readable output (the report dict) instead of text.

Suggestion mechanics (coarse on purpose — the model only needs to be right
within ~2x):
- h2d / d2h bandwidth terms: predicted term seconds vs the observed span
  seconds give a ratio r = observed/predicted; the bandwidth knob scales by
  1/r (taking the MEDIAN over samples so one jittered dispatch can't swing
  the suggestion).
- rtt: the observed per-dispatch dispatch-span floor (min over samples) —
  the fixed price a dispatch pays even when compute is negligible.
- compute rates: the observed dispatch window (launch + on-device compute,
  minus the calibrated per-dispatch rtt floor) vs the predicted compute term
  scales the site's rate knob (MM_RATE for agg/join sites, MM_CELL_RATE for
  grouped, UDF_FLOPS for udf).
"""

from __future__ import annotations

import argparse
import json
import sys
from typing import Dict, List, Optional

# site -> the compute-rate knob its residual calibrates
_SITE_RATE_KNOB = {
    "agg": "DAFT_TPU_COST_MM_RATE",
    "grouped agg": "DAFT_TPU_COST_MM_CELL_RATE",
    "join agg": "DAFT_TPU_COST_MM_RATE",
    "join topn": "DAFT_TPU_COST_MM_RATE",
    "mesh tier": "DAFT_TPU_COST_MM_RATE",
    "udf": "DAFT_TPU_COST_UDF_FLOPS",
}

# bandwidth-term -> knob; suggested value = current * predicted/observed
_BW_KNOBS = {"h2d": "DAFT_TPU_COST_H2D", "d2h": "DAFT_TPU_COST_D2H"}

_MIN_TERM_S = 1e-5   # ignore sub-10µs predictions/observations: pure noise


def _median(xs: List[float]) -> float:
    xs = sorted(xs)
    return xs[len(xs) // 2]


def _samples(records: List[dict]) -> List[dict]:
    """Records that carry both a priced breakdown for the chosen tier and an
    observed timing (a dispatched device/mesh stage with feedback)."""
    out = []
    for r in records:
        obs = r.get("observed")
        if not obs or obs.get("fallback"):
            continue
        if obs.get("spans_dropped"):
            # the feedback tee lost spans: the per-term sums are truncated
            # and the total fell back to the wall clock — not a calibration
            # sample (wall time includes upstream host work)
            continue
        chosen = r.get("chosen", "")
        pred = r.get(chosen) if chosen in ("device", "mesh") else None
        if not pred or not pred.get("total"):
            continue
        out.append({"site": r.get("site", "?"), "pred": pred, "obs": obs,
                    "pallas": r.get("pallas"),
                    "rows_pred": r.get("rows", 0),
                    "error_ratio": r.get("error_ratio")})
    return out


def suggest(records: List[dict],
            calibration: Optional[Dict[str, float]] = None) -> dict:
    """The report dict: per-term observed/predicted ratios, sample counts,
    and suggested cost-model env override values."""
    from ..ops.costmodel import calibration_dict

    cal = calibration if calibration is not None else calibration_dict()
    samples = _samples(records)
    report: dict = {
        "samples": len(samples),
        "records": len(records),
        "calibration": cal,
        "terms": {},
        "suggestions": {},
    }
    if not samples:
        return report

    # bandwidth terms: ratio of observed to predicted seconds per sample
    cal_bw = {"h2d": cal.get("h2d_bytes_per_s"), "d2h": cal.get("d2h_bytes_per_s")}
    for term, knob in _BW_KNOBS.items():
        ratios = []
        for s in samples:
            p, o = s["pred"].get(term, 0.0), s["obs"].get(term, 0.0)
            if p > _MIN_TERM_S and o > _MIN_TERM_S:
                ratios.append(o / p)
        if ratios:
            r = _median(ratios)
            report["terms"][term] = {"samples": len(ratios),
                                     "observed_over_predicted": round(r, 4)}
            cur = cal_bw.get(term)
            if cur:
                report["suggestions"][knob] = f"{cur / r:.4g}"

    # rtt: the fixed per-dispatch floor — min observed dispatch span per
    # dispatch (min, not median: compute rides inside the dispatch window,
    # so the floor is the best estimate of the pure round trip)
    rtts = []
    for s in samples:
        d, n = s["obs"].get("dispatch", 0.0), s["obs"].get("dispatches", 0)
        if d > _MIN_TERM_S and n:
            rtts.append(d / n)
    if rtts:
        floor = min(rtts)
        report["terms"]["rtt"] = {"samples": len(rtts),
                                  "observed_floor_s": round(floor, 6)}
        pred_rtt = cal.get("rtt_s")
        # only suggest when the observation disagrees with the calibration by
        # more than 2x — within 2x the decision is already right by contract
        if pred_rtt and (floor > 2 * pred_rtt or floor < pred_rtt / 2):
            report["suggestions"]["DAFT_TPU_COST_RTT"] = f"{floor:.6g}"

    # compute rates, per site: the dispatch window (launch + on-device
    # compute) minus the calibrated per-dispatch rtt floor, vs the predicted
    # compute term. The dispatch SPAN is the device-seconds observation —
    # the wall window would conflate upstream scan/decode time with compute.
    cal_rtt = cal.get("rtt_s") or 0.0
    per_site: Dict[str, List[float]] = {}
    for s in samples:
        pred_c = s["pred"].get("compute", 0.0)
        obs = s["obs"]
        n_disp = obs.get("dispatches", 0)
        residual = obs.get("dispatch", 0.0) - n_disp * cal_rtt
        if pred_c > _MIN_TERM_S and residual > _MIN_TERM_S:
            per_site.setdefault(s["site"], []).append(residual / pred_c)
    for site, ratios in per_site.items():
        r = _median(ratios)
        report["terms"][f"compute[{site}]"] = {
            "samples": len(ratios), "observed_over_predicted": round(r, 4)}
        knob = _SITE_RATE_KNOB.get(site)
        if knob and (r > 2 or r < 0.5):
            # a rate knob scales inversely with observed seconds
            base = {"DAFT_TPU_COST_MM_RATE": cal.get("mm_plane_rows_per_s"),
                    "DAFT_TPU_COST_MM_CELL_RATE": cal.get("mm_cell_rate"),
                    "DAFT_TPU_COST_UDF_FLOPS":
                        cal.get("udf_device_flops_per_s")}.get(knob)
            if base:
                report["suggestions"][knob] = f"{base / r:.4g}"

    # mesh terms (the ICI tier): calibrated from samples whose chosen tier
    # was the mesh — the observed dispatch window carries the multi-device
    # launch premium AND the collective, so the premium comes from the
    # per-dispatch floor (minus the single-chip rtt) and the ICI bandwidth
    # from the residual after premium + predicted compute are subtracted.
    cal_rtt = cal.get("rtt_s") or 0.0
    mesh_samples = [s for s in samples
                    if s["pred"].get("mesh_dispatch") is not None]
    floors = []
    for s in mesh_samples:
        d, n = s["obs"].get("dispatch", 0.0), s["obs"].get("dispatches", 0)
        if d > _MIN_TERM_S and n:
            floors.append(max(d / n - cal_rtt, 0.0))
    if floors:
        floor = min(floors)
        report["terms"]["mesh_dispatch"] = {
            "samples": len(floors), "observed_floor_s": round(floor, 6)}
        cur = cal.get("mesh_dispatch_s")
        if cur and floor > _MIN_TERM_S \
                and (floor > 2 * cur or floor < cur / 2):
            report["suggestions"]["DAFT_TPU_COST_MESH_DISPATCH"] = \
                f"{floor:.6g}"
    ici_ratios = []
    cal_meshd = cal.get("mesh_dispatch_s") or 0.0
    for s in mesh_samples:
        pred_ici = s["pred"].get("ici", 0.0)
        n_disp = s["obs"].get("dispatches", 0)
        residual = (s["obs"].get("dispatch", 0.0)
                    - n_disp * (cal_rtt + cal_meshd)
                    - s["pred"].get("compute", 0.0))
        if pred_ici > _MIN_TERM_S and residual > _MIN_TERM_S:
            ici_ratios.append(residual / pred_ici)
    if ici_ratios:
        r = _median(ici_ratios)
        report["terms"]["ici"] = {"samples": len(ici_ratios),
                                  "observed_over_predicted": round(r, 4)}
        cur = cal.get("ici_bytes_per_s")
        if cur and (r > 2 or r < 0.5):
            report["suggestions"]["DAFT_TPU_COST_ICI"] = f"{cur / r:.4g}"

    # pallas terms (the kernel tier): every device decision carries the
    # Pallas arm as a what-if side, but the breakdown only describes the
    # dispatched work when the arm actually won its gate — approximated
    # here as "pallas total under the chosen tier's total", the same
    # preference the auto gates apply. Two rates, two sample shapes: the
    # segment-reduce rate (DAFT_TPU_COST_PALLAS_RATE) calibrates from
    # grouped-shaped samples (compute term, no probe) via the plain
    # dispatch residual; the join-probe rate
    # (DAFT_TPU_COST_PALLAS_PROBE_RATE) from join-shaped samples (probe
    # term present) via the residual left after the predicted reduce is
    # subtracted — the ici mechanics, one level down.
    comp_ratios: List[float] = []
    probe_ratios: List[float] = []
    for s in samples:
        pw = s.get("pallas")
        if not pw or not pw.get("total") \
                or pw["total"] > s["pred"].get("total", 0.0):
            continue
        n_disp = s["obs"].get("dispatches", 0)
        residual = s["obs"].get("dispatch", 0.0) - n_disp * cal_rtt
        pred_c = pw.get("compute", 0.0)
        pred_p = pw.get("probe", 0.0)
        if pred_p > _MIN_TERM_S:
            rp = residual - pred_c
            if rp > _MIN_TERM_S:
                probe_ratios.append(rp / pred_p)
        elif pred_c > _MIN_TERM_S and residual > _MIN_TERM_S:
            comp_ratios.append(residual / pred_c)
    for ratios, term, knob, cal_key in (
            (comp_ratios, "pallas_compute", "DAFT_TPU_COST_PALLAS_RATE",
             "pallas_cell_rate"),
            (probe_ratios, "pallas_probe", "DAFT_TPU_COST_PALLAS_PROBE_RATE",
             "pallas_probe_cell_rate")):
        if not ratios:
            continue
        r = _median(ratios)
        report["terms"][term] = {"samples": len(ratios),
                                 "observed_over_predicted": round(r, 4)}
        cur = cal.get(cal_key)
        if cur and (r > 2 or r < 0.5):
            report["suggestions"][knob] = f"{cur / r:.4g}"

    errs = [s["error_ratio"] for s in samples
            if s.get("error_ratio") is not None]
    if errs:
        report["error_ratio_median"] = round(_median(errs), 4)
    return report


def render(report: dict) -> str:
    lines = ["== Cost-model calibration report =="]
    lines.append(f"records: {report['records']}, "
                 f"observed-vs-predicted samples: {report['samples']}")
    if report.get("error_ratio_median") is not None:
        lines.append(f"model error (median observed/predicted s/row): "
                     f"{report['error_ratio_median']}x")
    cal = report.get("calibration") or {}
    if cal:
        lines.append("calibration in effect:")
        for k, v in sorted(cal.items()):
            lines.append(f"  {k:<24} {v:g}")
    if report["terms"]:
        lines.append("per-term observed vs predicted:")
        for term, t in sorted(report["terms"].items()):
            detail = ", ".join(f"{k}={v}" for k, v in t.items())
            lines.append(f"  {term:<18} {detail}")
    if report["suggestions"]:
        lines.append("suggested overrides (export before the next run):")
        for knob, val in sorted(report["suggestions"].items()):
            lines.append(f"  export {knob}={val}")
    else:
        lines.append("no overrides suggested"
                     + (" (no samples — run a device workload first, or pass"
                        " --ledger FILE.json)" if not report["samples"]
                        else " (model within 2x everywhere — calibrated)"))
    return "\n".join(lines)


def _probe_workload(rows: int) -> None:
    """Populate the process ledger: forced device runs of the agg shapes the
    cost model prices (ungrouped filter+agg, grouped agg), each priced via
    DAFT_TPU_PLACEMENT_PRICE_FORCED so predicted-vs-observed samples exist
    on ANY backend (join/udf sites calibrate from real-workload ledgers via
    --ledger)."""
    import daft_tpu
    from daft_tpu import col
    from daft_tpu.config import execution_config_ctx

    df = daft_tpu.from_pydict({
        "k": [i % 97 for i in range(rows)],
        "v": [float(i % 8191) for i in range(rows)],
        "w": [float(i % 31) for i in range(rows)],
    })
    with execution_config_ctx(device_mode="on", device_min_rows=1,
                              mesh_devices=1):
        # ungrouped filter+agg (the Q6 shape), twice: the second run hits
        # resident planes, sampling the warm-path h2d credit too
        for _ in range(2):
            df.where(col("w") > 4).agg(col("v").sum().alias("s"),
                                       col("v").min().alias("lo"),
                                       col("v").max().alias("hi")).to_pydict()
        # grouped agg (the Q1 shape)
        for _ in range(2):
            (df.groupby("k").agg(col("v").sum().alias("s"),
                                 col("v").mean().alias("m"),
                                 col("v").count().alias("n"))
               .sort("k").to_pydict())


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m daft_tpu.tools.calibrate",
        description="Replay placement-ledger observed-vs-predicted samples "
                    "into suggested cost-model env overrides.")
    ap.add_argument("--ledger", help="read records from a ledger JSON dump "
                                     "instead of running the probe workload")
    ap.add_argument("--rows", type=int, default=65_536,
                    help="probe workload rows (default 65536)")
    ap.add_argument("--json", action="store_true", dest="as_json",
                    help="machine-readable report")
    args = ap.parse_args(argv)

    calibration = None
    if args.ledger:
        with open(args.ledger) as f:
            data = json.load(f)
        records = data["records"] if isinstance(data, dict) else data
        if isinstance(data, dict) and data.get("calibration"):
            calibration = data["calibration"]
    else:
        import os

        os.environ["DAFT_TPU_PLACEMENT_PRICE_FORCED"] = "1"
        try:
            _probe_workload(args.rows)
        finally:
            os.environ.pop("DAFT_TPU_PLACEMENT_PRICE_FORCED", None)
        from ..observability.placement import ledger

        records = ledger().snapshot()

    report = suggest(records, calibration)
    if args.as_json:
        print(json.dumps(report))
    else:
        print(render(report))
    return 0


if __name__ == "__main__":
    sys.exit(main())
