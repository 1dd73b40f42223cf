#!/usr/bin/env python
"""mxtpu-doctor: automated bottleneck & regression diagnosis.

Joins the signals the stack already emits — the attribution plane's
``step.phases`` records, the PR-7 ``introspect.cost`` roofline, the
watchdog's ``anomaly`` instants, and the serving request phase spans —
into one ranked verdict per workload instead of five metric families a
human reads side by side::

    python tools/mxtpu_doctor.py trace.jsonl
    python tools/mxtpu_doctor.py trace.jsonl --json
    python tools/mxtpu_doctor.py --env

Verdict vocabulary (training sites): ``input_bound`` (the accelerator
idles on the host input pipeline), ``comm_bound`` (exposed gradient
communication), ``host_bound`` (python/bookkeeping/checkpoint residual),
``compute_memory_bound`` / ``compute_flops_bound`` (the device itself,
split at the roofline ridge point when cost analysis is available).
Every verdict carries evidence lines ("input_wait = 34% of step") and a
concrete knob recipe ("raise MXTPU_DEVICE_PREFETCH ...").

``trace.jsonl`` is a ring dump (``observability.dump_jsonl``).
``--env`` is the ported legacy MXNet environment checker
(``diagnose.py``): backend visibility + env sanity.

Pure stdlib for trace analysis (runs on CI artifact hosts without jax);
only ``--env`` imports jax/mxnet_tpu, best-effort.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

PHASES = ("input_wait", "h2d", "ckpt_overhead", "comm_exposed",
          "compute", "host_gap")

#: verdict -> (one-line meaning, concrete knob recipe)
RECIPES = {
    "input_bound": (
        "the accelerator idles waiting on the host input pipeline",
        "feed through gluon.data.StreamReader and widen its decode "
        "pool (MXTPU_STREAM_DECODE_THREADS) if decode-bound, raise "
        "its prefetch depth (MXTPU_STREAM_READAHEAD) and shard "
        "parallelism (more/smaller shards) if storage-bound — "
        "mxtpu_stream_decode_wait_seconds_total tells which; then "
        "raise MXTPU_DEVICE_PREFETCH (staging queue depth) "
        "(docs/performance.md 'Streaming input')"),
    "comm_bound": (
        "gradient communication is exposed, not hidden behind compute",
        "use the bucket-ready overlapped comm mode (MXTPU_OVERLAP=ready) "
        "and/or raise MXTPU_OVERLAP_BUCKET_BYTES so collectives overlap "
        "the backward (docs/performance.md)"),
    "host_bound": (
        "per-step host work (python, bookkeeping, checkpoint entry) "
        "dominates",
        "raise superstep K (MXTPU_SUPERSTEP_K) to amortize the host "
        "loop, widen the checkpoint interval, and keep logging/metrics "
        "reads off the step path"),
    "compute_memory_bound": (
        "the device itself is busy and HBM-bandwidth limited",
        "cut memory traffic: bf16/AMP activations, fuse steps "
        "(superstep), raise arithmetic intensity (bigger batch, fused "
        "optimizer) — more FLOPs won't help below the ridge point"),
    "compute_flops_bound": (
        "the device itself is busy at its compute roof",
        "this is the healthy bottleneck: scale out (SPMD mesh), or cut "
        "work (mixed precision, smaller model/seq) — host knobs won't "
        "move it"),
    "serving_queue_bound": (
        "requests spend their latency waiting for admission/batching",
        "raise max_batch / shrink max_wait on the ContinuousBatcher, "
        "add bucket capacity, or scale serving replicas"),
    "pipeline_bubble_bound": (
        "pipeline ranks idle in schedule fill/drain bubbles",
        "raise microbatches per step (MXTPU_PIPELINE_MICROBATCHES) or "
        "run the interleaved schedule (MXTPU_PIPELINE_SCHEDULE="
        "interleaved, stages a multiple of the pp axis) — the bubble "
        "shrinks as (S-1)/(M*v + S-1); plain 1f1b matches gpipe's "
        "bubble and only cuts activation-stash memory "
        "(docs/performance.md)"),
    "healthy": (
        "no phase dominates the step budget",
        "nothing to do — re-run with a longer window if this "
        "contradicts observed slowness"),
}

#: attribution site -> introspect.cost site for the roofline join
_COST_SITES = {"trainer": ("trainer_fused",), "superstep": ("superstep",),
               "spmd": ("spmd_step",), "spmd_superstep": ("spmd_superstep",),
               "spmd_staged": ("spmd_step",)}

# verdict thresholds (fractions of the mean step period) — loose by
# design: the doctor ranks, tests pin the contract on seeded extremes
_INPUT_FRAC = 0.25
_COMM_FRAC = 0.20
_HOST_FRAC = 0.30


def load_events(source) -> list:
    """Events from a JSONL ring dump, a chrome ``{"traceEvents"}`` doc,
    or a flight bundle (``{"trace_events"}``) — path or text."""
    if isinstance(source, str) and "\n" not in source \
            and os.path.exists(source):
        with open(source) as f:
            text = f.read()
    else:
        text = source
    try:
        doc = json.loads(text)
    except ValueError:
        return [json.loads(line) for line in text.splitlines()
                if line.strip()]
    if isinstance(doc, dict):
        return list(doc.get("traceEvents") or doc.get("trace_events") or [])
    return list(doc)


def _num(d, key):
    v = d.get(key) if isinstance(d, dict) else None
    return float(v) if isinstance(v, (int, float)) else None


# ---------------------------------------------------------------------------
# training verdicts (from step.phases attribution spans)
# ---------------------------------------------------------------------------

def phase_summary(events, site=None) -> dict:
    """site -> mean per-step phase seconds (weighted by each record's
    K) + ``step_s`` and ``count``, from the ``step.phases`` spans."""
    acc = {}
    for ev in events:
        if ev.get("name") != "step.phases":
            continue
        args = ev.get("args")
        if not isinstance(args, dict):
            continue
        s = str(args.get("site", "?"))
        if site is not None and s != site:
            continue
        k = max(int(_num(args, "k") or 1), 1)
        period = _num(args, "period_ms")
        if period is None:
            continue
        slot = acc.setdefault(s, {"k": 0, "period": 0.0, "n": 0,
                                  **{ph: 0.0 for ph in PHASES}})
        slot["k"] += k
        slot["n"] += 1
        slot["period"] += period / 1e3  # whole-dispatch period
        for ph in PHASES:
            v = _num(args, f"{ph}_ms")
            if v is not None:
                slot[ph] += v / 1e3 * k  # args are per-step amortized
    out = {}
    for s, slot in acc.items():
        kk = max(slot["k"], 1)
        out[s] = {ph: slot[ph] / kk for ph in PHASES}
        out[s]["step_s"] = slot["period"] / kk
        out[s]["count"] = slot["k"]
        out[s]["dispatches"] = slot["n"]
    return out


def _roofline_bound(events, site):
    """('compute_memory_bound'|'compute_flops_bound', evidence) from the
    last ``introspect.cost`` record matching the attribution site, or
    (None, None) when no usable cost analysis is in the dump."""
    wanted = _COST_SITES.get(site, (site,))
    rec = None
    for ev in events:
        if ev.get("name") != "introspect.cost":
            continue
        args = ev.get("args")
        if isinstance(args, dict) and args.get("site") in wanted:
            rec = args  # last one wins
    if rec is None:
        return None, None
    ai = _num(rec, "arith_intensity")
    peak = _num(rec, "peak_tflops")
    bw = _num(rec, "peak_hbm_gbs")
    if ai is None or not peak or not bw:
        return None, None
    ridge = peak * 1e12 / (bw * 1e9)
    if ai < ridge:
        return ("compute_memory_bound",
                f"arith intensity {ai:.1f} FLOP/B below the device "
                f"ridge {ridge:.1f} (cost analysis, site "
                f"{rec.get('site')})")
    return ("compute_flops_bound",
            f"arith intensity {ai:.1f} FLOP/B above the device ridge "
            f"{ridge:.1f} (cost analysis, site {rec.get('site')})")


def training_verdicts(events) -> list:
    """One ranked verdict dict per attribution site seen in the trace."""
    anomalies = anomaly_counts(events)
    out = []
    for site, ph in sorted(phase_summary(events).items()):
        step = ph["step_s"]
        if step <= 0:
            continue

        def pct(name):
            return ph[name] / step * 100.0

        def ms(name):
            return ph[name] * 1e3

        evidence = [
            f"{name} = {pct(name):.1f}% of step "
            f"({ms(name):.3f} ms of {step * 1e3:.3f} ms/step)"
            for name in PHASES if ph[name] > 0.0005 * step]
        host_share = (ph["host_gap"] + ph["ckpt_overhead"]) / step
        if ph["input_wait"] / step >= _INPUT_FRAC:
            verdict = "input_bound"
            if anomalies.get("input_wait"):
                evidence.append(
                    f"watchdog fired input_wait x"
                    f"{anomalies['input_wait']} on this run")
        elif ph["comm_exposed"] / step >= _COMM_FRAC:
            verdict = "comm_bound"
        elif host_share >= _HOST_FRAC and \
                ph["compute"] / step < (1.0 - _HOST_FRAC):
            verdict = "host_bound"
        elif ph["compute"] / step >= 0.5:
            verdict, why = _roofline_bound(events, site)
            if verdict is None:
                verdict = "compute_flops_bound"
                evidence.append(
                    "no cost-analysis record for this site — defaulting "
                    "the compute split to flops-bound (enable "
                    "MXTPU_INTROSPECT for the memory/flops ridge test)")
            else:
                evidence.append(why)
        else:
            verdict = "healthy"
        meaning, recipe = RECIPES[verdict]
        out.append({
            "site": site, "verdict": verdict, "meaning": meaning,
            "recipe": recipe, "evidence": evidence,
            "step_ms": round(step * 1e3, 4),
            "steps": int(ph["count"]),
            "phases_ms": {n: round(ms(n), 4) for n in PHASES},
            "fractions": {n: round(ph[n] / step, 4) for n in PHASES},
        })
    # rank: unhealthy first, by how dominant the offending share is
    sev = {"healthy": 0.0}
    for v in out:
        if v["verdict"] != "healthy":
            sev[v["site"]] = 1.0 - v["fractions"]["compute"]
    out.sort(key=lambda v: (v["verdict"] == "healthy",
                            -sev.get(v["site"], 0.0)))
    return out


# ---------------------------------------------------------------------------
# serving verdicts (from serving.request phase spans)
# ---------------------------------------------------------------------------

_SERVE_PHASES = ("queue", "batch", "dispatch", "slice")


def serving_verdicts(events) -> list:
    """One verdict per served model from the per-request phase spans."""
    by_model = {}
    for ev in events:
        if ev.get("name") != "serving.request":
            continue
        args = ev.get("args")
        if not isinstance(args, dict):
            continue
        slot = by_model.setdefault(str(args.get("model", "?")),
                                   {"n": 0,
                                    **{p: 0.0 for p in _SERVE_PHASES}})
        slot["n"] += 1
        for p in _SERVE_PHASES:
            v = _num(args, f"{p}_ms")
            if v is not None:
                slot[p] += v
    out = []
    for model, slot in sorted(by_model.items()):
        n = max(slot["n"], 1)
        mean = {p: slot[p] / n for p in _SERVE_PHASES}
        total = sum(mean.values())
        if total <= 0:
            continue
        dominant = max(_SERVE_PHASES, key=lambda p: mean[p])
        if dominant in ("queue", "batch") and \
                (mean["queue"] + mean["batch"]) / total >= 0.5:
            verdict = "serving_queue_bound"
        elif dominant == "dispatch":
            verdict = "compute_flops_bound"
        else:
            verdict = "host_bound"
        meaning, recipe = RECIPES[verdict]
        evidence = [f"{p} = {mean[p] / total * 100:.1f}% of request "
                    f"latency ({mean[p]:.3f} ms mean)"
                    for p in _SERVE_PHASES if mean[p] > 0]
        out.append({"model": model, "verdict": verdict,
                    "meaning": meaning, "recipe": recipe,
                    "evidence": evidence, "requests": slot["n"],
                    "phases_ms": {p: round(mean[p], 4)
                                  for p in _SERVE_PHASES}})
    return out


def anomaly_counts(events) -> dict:
    """Watchdog firings by kind, from the ``anomaly`` trace instants."""
    out = {}
    for ev in events:
        if ev.get("name") != "anomaly":
            continue
        args = ev.get("args")
        kind = str(args.get("kind", "-")) if isinstance(args, dict) else "-"
        if kind != "summary":
            out[kind] = out.get(kind, 0) + 1
    return out


#: bubble threshold for the pipeline verdict — a tuned interleaved
#: schedule sits well under this; fill-drain at few microbatches does not
_BUBBLE_FRAC = 0.15


def pipeline_schedule_records(events) -> list:
    """The ``pipeline.schedule`` instants a pipeline step publishes at
    build time (measured bubble per realized schedule)."""
    out = []
    for ev in events:
        if ev.get("name") != "pipeline.schedule":
            continue
        args = ev.get("args") if isinstance(ev.get("args"), dict) else {}
        bf = _num(args, "bubble_fraction")
        if bf is None:
            continue
        out.append({"schedule": str(args.get("schedule", "-")),
                    "bubble_fraction": bf,
                    "ticks": args.get("ticks"),
                    "stash_slots": args.get("stash_slots")})
    return out


def pipeline_verdicts(events) -> list:
    """``pipeline_bubble_bound``: the schedule gauge says ranks idle in
    fill/drain, joined against the phase spans — host-side attribution
    books that idle as device compute, so a compute-dominated site with
    a fat bubble is really schedule-bound, not flops-bound."""
    recs = pipeline_schedule_records(events)
    if not recs:
        return []
    worst = max(recs, key=lambda r: r["bubble_fraction"])
    if worst["bubble_fraction"] < _BUBBLE_FRAC:
        return []
    evidence = [
        f"schedule {worst['schedule']}: bubble_fraction = "
        f"{worst['bubble_fraction']:.3f} over {worst['ticks']} ticks "
        f"(stash_slots = {worst['stash_slots']})"]
    for site, ph in sorted(phase_summary(events).items()):
        step = ph["step_s"]
        if step > 0 and ph["compute"] / step >= 0.5:
            evidence.append(
                f"site {site} looks compute-bound from the host "
                f"({ph['compute'] / step * 100:.1f}% of step) but "
                f"{worst['bubble_fraction'] * 100:.0f}% of that device "
                "time is pipeline fill/drain idle")
    meaning, recipe = RECIPES["pipeline_bubble_bound"]
    return [{"site": "pipeline", "verdict": "pipeline_bubble_bound",
             "meaning": meaning, "recipe": recipe,
             "schedule": worst["schedule"],
             "bubble_fraction": round(worst["bubble_fraction"], 6),
             "evidence": evidence}]


def diagnose(events) -> dict:
    """The full machine-readable report for one trace."""
    training = training_verdicts(events)
    serving = serving_verdicts(events)
    pipeline = pipeline_verdicts(events)
    report = {
        "format": "mxtpu-doctor-v1",
        "training": training,
        "serving": serving,
        "pipeline": pipeline,
        "anomalies": anomaly_counts(events),
    }
    ranked = [v for v in training if v["verdict"] != "healthy"]
    # a fat bubble explains a compute-bound site (the idle is booked as
    # device compute), so it outranks the roofline verdicts — but not
    # input/comm/host starvation, which the schedule can't cause
    if pipeline and (not ranked
                     or ranked[0]["verdict"].startswith("compute_")):
        report["top"] = {"site": "pipeline",
                         "verdict": pipeline[0]["verdict"]}
    elif ranked:
        report["top"] = {"site": ranked[0]["site"],
                         "verdict": ranked[0]["verdict"]}
    elif training:
        report["top"] = {"site": training[0]["site"],
                         "verdict": training[0]["verdict"]}
    elif serving:
        report["top"] = {"site": f"serving:{serving[0]['model']}",
                         "verdict": serving[0]["verdict"]}
    return report


def render(report) -> str:
    """Human-readable rendering of :func:`diagnose`'s output."""
    lines = ["mxtpu-doctor diagnosis:"]
    for v in report["training"]:
        lines.append(f"\n  [{v['site']}] verdict: {v['verdict']} — "
                     f"{v['meaning']}")
        lines.append(f"    {v['steps']} steps @ {v['step_ms']:.3f} "
                     f"ms/step")
        for e in v["evidence"]:
            lines.append(f"    evidence: {e}")
        lines.append(f"    recipe: {v['recipe']}")
    for v in report.get("pipeline", []):
        lines.append(f"\n  [pipeline] verdict: {v['verdict']} — "
                     f"{v['meaning']}")
        lines.append(f"    schedule {v['schedule']}, bubble_fraction "
                     f"{v['bubble_fraction']:.3f}")
        for e in v["evidence"]:
            lines.append(f"    evidence: {e}")
        lines.append(f"    recipe: {v['recipe']}")
    for v in report["serving"]:
        lines.append(f"\n  [serving:{v['model']}] verdict: "
                     f"{v['verdict']} — {v['meaning']}")
        lines.append(f"    {v['requests']} requests")
        for e in v["evidence"]:
            lines.append(f"    evidence: {e}")
        lines.append(f"    recipe: {v['recipe']}")
    if report["anomalies"]:
        kinds = ", ".join(f"{k} x{n}"
                          for k, n in sorted(report["anomalies"].items()))
        lines.append(f"\n  watchdog anomalies: {kinds}")
    if not report["training"] and not report["serving"]:
        lines.append(
            "  no step.phases / serving.request events in this trace — "
            "arm telemetry (MXTPU_TELEMETRY=1; attribution is on by "
            "default with it) and re-capture")
    return "\n".join(lines)


# ---------------------------------------------------------------------------
# --env: the ported tools/diagnose.py environment checker
# ---------------------------------------------------------------------------

_ENV_PREFIXES = ("MXTPU_", "JAX_", "XLA_", "DMLC_", "TPU_")


def env_report() -> dict:
    """Backend visibility + env sanity (the still-relevant half of the
    retired legacy ``tools/diagnose.py``), with doctor-style warnings."""
    import platform

    report = {"format": "mxtpu-doctor-env-v1",
              "python": sys.version.split()[0],
              "platform": platform.platform(),
              "env": {k: v for k, v in sorted(os.environ.items())
                      if k.startswith(_ENV_PREFIXES)},
              "warnings": []}
    try:
        import jax

        report["jax"] = {"version": jax.__version__,
                         "backend": jax.default_backend(),
                         "devices": [str(d) for d in jax.devices()],
                         "process_index": jax.process_index(),
                         "process_count": jax.process_count()}
        if not jax.devices():
            report["warnings"].append("jax sees no devices")
    except Exception as e:
        report["jax"] = None
        report["warnings"].append(f"jax unavailable: {e}")
    try:
        import mxnet_tpu as mx
        from mxnet_tpu import runtime
        from mxnet_tpu.ops.registry import all_ops

        feats = runtime.Features()
        report["mxnet_tpu"] = {
            "version": getattr(mx, "__version__", "dev"),
            "ops": len(all_ops()),
            "features": {k: bool(getattr(f, "enabled", False))
                         for k, f in sorted(feats.items())}}
        telemetry = mx.observability.ENABLED
        if not telemetry:
            report["warnings"].append(
                "MXTPU_TELEMETRY is off — attribution, watchdog and the "
                "flight recorder are all dark")
        elif not mx.observability.attribution.ENABLED:
            report["warnings"].append(
                "MXTPU_ATTRIBUTION=0 — per-phase step accounting is off "
                "while telemetry is on")
    except Exception as e:
        report["mxnet_tpu"] = None
        report["warnings"].append(f"mxnet_tpu unavailable: {e}")
    return report


def render_env(report) -> str:
    lines = ["mxtpu-doctor --env:",
             f"  python {report['python']} on {report['platform']}"]
    jx = report.get("jax")
    if jx:
        lines.append(f"  jax {jx['version']}: backend {jx['backend']}, "
                     f"{len(jx['devices'])} device(s), process "
                     f"{jx['process_index']}/{jx['process_count']}")
        for d in jx["devices"][:8]:
            lines.append(f"    {d}")
    mxi = report.get("mxnet_tpu")
    if mxi:
        on = [f for f, en in mxi["features"].items() if en]
        lines.append(f"  mxnet_tpu: {mxi['ops']} nd ops; features on: "
                     f"{', '.join(on) or '-'}")
    if report["env"]:
        lines.append("  environment:")
        for k, v in report["env"].items():
            lines.append(f"    {k}={v}")
    for w in report["warnings"]:
        lines.append(f"  WARNING: {w}")
    if not report["warnings"]:
        lines.append("  environment looks sane")
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="bottleneck & regression diagnosis over mxnet_tpu "
                    "telemetry artifacts")
    ap.add_argument("trace", nargs="?", default=None,
                    help="telemetry trace (JSONL ring dump, chrome "
                         "trace, or flight bundle); '-' for stdin")
    ap.add_argument("--json", action="store_true",
                    help="machine-readable output")
    ap.add_argument("--site", default=None,
                    help="only report this attribution site "
                         "(trainer / superstep / spmd / ...)")
    ap.add_argument("--env", action="store_true",
                    help="environment & backend sanity report (the "
                         "ported legacy tools/diagnose.py)")
    args = ap.parse_args(argv)

    if args.env:
        report = env_report()
        print(json.dumps(report, indent=2, default=str) if args.json
              else render_env(report))
        return 0
    if not args.trace:
        ap.error("need a trace file (or --env)")
    source = sys.stdin.read() if args.trace == "-" else args.trace
    events = load_events(source)
    report = diagnose(events)
    if args.site:
        report["training"] = [v for v in report["training"]
                              if v["site"] == args.site]
    print(json.dumps(report, indent=2, default=str) if args.json
          else render(report))
    return 0


if __name__ == "__main__":
    sys.exit(main())
