#!/usr/bin/env python
"""Pretty-print a captured telemetry JSONL trace as an aggregate table.

Takes the JSONL emitted by ``mxnet_tpu.observability.dump_jsonl()`` and
renders the ``profiler.dumps``-style table (Name / Total Count /
Time (ms) / Min / Max / Avg), aggregated per event name::

    python tools/telemetry_report.py trace.jsonl
    python tools/telemetry_report.py trace.jsonl --cat train --sort avg

Pure stdlib on purpose — the report runs anywhere (CI artifact hosts,
laptops without jax) and in milliseconds.
"""

from __future__ import annotations

import argparse
import json
import sys

COLUMNS = (f"{'Name':<40}{'Total Count':>12}{'Time (ms)':>14}"
           f"{'Min (ms)':>12}{'Max (ms)':>12}{'Avg (ms)':>12}"
           f"{'Bytes':>14}")

_SORTS = {
    "total": lambda kv: kv[1][1],
    "count": lambda kv: kv[1][0],
    "min": lambda kv: kv[1][2],
    "max": lambda kv: kv[1][3],
    "avg": lambda kv: kv[1][1] / kv[1][0] if kv[1][0] else 0.0,
    "bytes": lambda kv: kv[1][4],
    "name": lambda kv: kv[0],
}


def _fmt_bytes(n):
    if not n:
        return "-"
    for unit in ("B", "KiB", "MiB", "GiB"):
        if n < 1024 or unit == "GiB":
            return f"{n:.1f}{unit}" if unit != "B" else f"{int(n)}B"
        n /= 1024.0
    return f"{n:.1f}GiB"  # pragma: no cover - loop always returns


def load_events(source):
    """Parse JSONL text or a path into a list of event dicts."""
    import os

    if "\n" not in source and os.path.exists(source):
        with open(source) as f:
            source = f.read()
    events = []
    for ln, line in enumerate(source.splitlines(), 1):
        line = line.strip()
        if not line:
            continue
        try:
            ev = json.loads(line)
        except json.JSONDecodeError as e:
            raise SystemExit(f"line {ln}: not valid JSON ({e})")
        if not isinstance(ev, dict) or "name" not in ev:
            raise SystemExit(f"line {ln}: not a trace event object")
        events.append(ev)
    return events


def load_source(source):
    """Path/text -> (events, cluster). A plain JSONL trace yields
    ``(events, None)``; a federation snapshot bundle (the JSON object
    ``observability.federation.dump_cluster_snapshot()`` writes, marked
    by its top-level ``federation`` key) yields the embedded trace
    events plus the cluster body — so the existing per-process sections
    AND the cluster sections render from the same file."""
    import os

    if "\n" not in source and os.path.exists(source):
        with open(source) as f:
            source = f.read()
    text = source.strip()
    if text.startswith("{"):
        try:
            body = json.loads(text)
        except json.JSONDecodeError:
            body = None
        if isinstance(body, dict) and body.get("federation"):
            events = [ev for ev in (body.get("events") or [])
                      if isinstance(ev, dict) and "name" in ev]
            return events, body
    return load_events(source), None


def aggregate(events, cat=None):
    """name -> [count, total_ms, min_ms, max_ms, bytes] over duration
    events. ``bytes`` sums the ``args.bytes`` payload some series carry
    (kvstore.allreduce, data.h2d); unknown series and non-dict args
    aggregate fine with 0 — the report never crashes on a new series."""
    agg = {}
    for ev in events:
        if cat and ev.get("cat") != cat:
            continue
        ms = float(ev.get("dur", 0.0)) / 1e3  # trace dur is microseconds
        args = ev.get("args")
        try:
            nbytes = float(args.get("bytes", 0)) if isinstance(args, dict) \
                else 0.0
        except (TypeError, ValueError):
            nbytes = 0.0
        rec = agg.get(ev["name"])
        if rec is None:
            agg[ev["name"]] = [1, ms, ms, ms, nbytes]
        else:
            rec[0] += 1
            rec[1] += ms
            rec[2] = min(rec[2], ms)
            rec[3] = max(rec[3], ms)
            rec[4] += nbytes
    return agg


def render_table(events, cat=None, sort_by="total", ascending=False):
    """The ``profiler.dumps(aggregate_stats=True)`` table, from a trace."""
    agg = aggregate(events, cat=cat)
    lines = ["Telemetry Trace Statistics:", COLUMNS]
    key = _SORTS.get(sort_by, _SORTS["total"])
    for name, (cnt, tot, mn, mx, nbytes) in sorted(agg.items(), key=key,
                                                   reverse=not ascending):
        lines.append(f"{name:<40}{cnt:>12}{tot:>14.4f}"
                     f"{mn:>12.4f}{mx:>12.4f}{tot / cnt:>12.4f}"
                     f"{_fmt_bytes(nbytes):>14}")
    if not agg:
        lines.append("(no events)")
    return "\n".join(lines)


def render_amp(events):
    """Mixed-precision summary from ``amp.scale_update`` events (the
    trace-side view of the ``mxtpu_amp_loss_scale`` /
    ``mxtpu_amp_overflow_total`` gauges). Crash-proof by construction:
    absent series -> empty string, malformed args render as '-'."""
    evs = [ev for ev in events if ev.get("name") == "amp.scale_update"]
    if not evs:
        return ""

    def arg(ev, key):
        args = ev.get("args")
        return args.get(key, "-") if isinstance(args, dict) else "-"

    overflows = sum(1 for ev in evs if arg(ev, "overflow") is True)
    last = evs[-1]
    return "\n".join([
        "", "AMP loss scaling:",
        f"  scale updates: {len(evs)}, overflows (skipped steps): "
        f"{overflows}, final scale: {arg(last, 'scale')}, "
        f"overflow total: {arg(last, 'overflow_total')}"])


def render_superstep(events):
    """Dispatches-per-step amortization from ``trainer.superstep``
    events (one event per K-step dispatch, ``args.k`` = its K). Same
    crash-proofing contract as the AMP section: absent series -> empty
    string, malformed args count as K=1."""
    evs = [ev for ev in events if ev.get("name") == "trainer.superstep"]
    if not evs:
        return ""

    def k_of(ev):
        args = ev.get("args")
        try:
            return max(1, int(args.get("k", 1))) if isinstance(args, dict) \
                else 1
        except (TypeError, ValueError):
            return 1

    steps = sum(k_of(ev) for ev in evs)
    return "\n".join([
        "", "Superstep amortization:",
        f"  {len(evs)} dispatches covering {steps} training steps -> "
        f"{len(evs) / steps:.3f} dispatches/step "
        f"(mean K = {steps / len(evs):.1f})"])


def render_serving(events):
    """Serving SLO summary from the ``serving.*`` trace series:
    ``serving.batch`` spans (one per continuous-batching dispatch,
    ``args``: model/bucket/n_valid/capacity/fill/queue_depth) joined
    with the ``serving.shed`` / ``serving.timeout`` instants and
    ``serving.swap`` version transitions. Same crash-proofing contract
    as the AMP/roofline sections: absent series -> empty string,
    malformed args render as '-' / count as zero."""
    batches = [ev for ev in events if ev.get("name") == "serving.batch"]
    sheds = sum(1 for ev in events if ev.get("name") == "serving.shed")
    timeouts = sum(1 for ev in events
                   if ev.get("name") == "serving.timeout")
    compiles = sum(1 for ev in events
                   if ev.get("name") == "serving.compile")
    swaps = [ev for ev in events if ev.get("name") == "serving.swap"]
    if not (batches or sheds or timeouts or compiles or swaps):
        return ""

    def num(ev, key):
        args = ev.get("args")
        v = args.get(key) if isinstance(args, dict) else None
        return float(v) if isinstance(v, (int, float)) else None

    def arg(ev, key):
        args = ev.get("args")
        return args.get(key, "-") if isinstance(args, dict) else "-"

    lines = ["", "Serving:"]
    # per-model dispatch stats from the batch spans
    per_model = {}
    for ev in batches:
        per_model.setdefault(str(arg(ev, "model")), []).append(ev)
    for model in sorted(per_model):
        evs = per_model[model]
        rows = sum(n for n in (num(e, "n_valid") for e in evs)
                   if n is not None)
        fills = [f for f in (num(e, "fill") for e in evs)
                 if f is not None]
        depths = [d for d in (num(e, "queue_depth") for e in evs)
                  if d is not None]
        fill = f"{sum(fills) / len(fills):.2f}" if fills else "-"
        depth = f"{max(depths):.0f}" if depths else "-"
        durs = [float(e.get("dur", 0.0)) / 1e3 for e in evs]
        avg = f"{sum(durs) / len(durs):.3f}" if durs else "-"
        lines.append(
            f"  {model}: {len(evs)} batches, {int(rows)} requests, "
            f"mean fill {fill}, peak queue depth {depth}, "
            f"avg dispatch {avg} ms")
    if sheds or timeouts:
        lines.append(f"  shed: {sheds}, deadline timeouts: {timeouts}")
    if compiles:
        lines.append(f"  AOT bucket compiles: {compiles} "
                     f"(flat after warmup by contract)")
    for ev in swaps:
        lines.append(
            f"  swap [{arg(ev, 'model')}] {arg(ev, 'outcome')}: "
            f"{arg(ev, 'prev_version')} -> {arg(ev, 'version')}")
    return "\n".join(lines)


def render_fleet(events):
    """Self-healing fleet summary from the ``fleet.*`` trace instants:
    ``fleet.brownout`` level transitions (``args``: model/level/prev)
    and ``fleet.autoscale`` actuations (``args``: model/action/n).
    Crash-proof like the serving section: absent series -> empty
    string, malformed args render as '-' / count as zero."""
    brownouts = [ev for ev in events
                 if ev.get("name") == "fleet.brownout"]
    actuations = [ev for ev in events
                  if ev.get("name") == "fleet.autoscale"]
    if not (brownouts or actuations):
        return ""

    def arg(ev, key):
        args = ev.get("args")
        return args.get(key, "-") if isinstance(args, dict) else "-"

    lines = ["", "Fleet:"]
    per_action = {}
    for ev in actuations:
        k = (str(arg(ev, "model")), str(arg(ev, "action")))
        per_action[k] = per_action.get(k, 0) + 1
    for (model, action) in sorted(per_action):
        lines.append(
            f"  autoscale [{model}] {action}: "
            f"{per_action[(model, action)]}")
    for ev in brownouts:
        lines.append(
            f"  brownout [{arg(ev, 'model')}] level "
            f"{arg(ev, 'prev')} -> {arg(ev, 'level')}")
    return "\n".join(lines)


#: the attribution plane's phase order (observability/attribution.py)
_PHASES = ("input_wait", "h2d", "ckpt_overhead", "comm_exposed",
           "compute", "host_gap")


def render_attribution(events):
    """'Attribution' section from the ``step.phases`` spans: per-site
    mean per-step phase table with % of step. Same crash-proofing
    contract as every other section: absent series -> empty string,
    malformed args are skipped, a zero period renders nothing."""
    acc = {}
    for ev in events:
        if ev.get("name") != "step.phases":
            continue
        args = ev.get("args")
        if not isinstance(args, dict):
            continue
        try:
            k = max(int(args.get("k", 1)), 1)
            period = float(args["period_ms"])
        except (KeyError, TypeError, ValueError):
            continue
        slot = acc.setdefault(str(args.get("site", "?")),
                              {"k": 0, "period": 0.0,
                               **{p: 0.0 for p in _PHASES}})
        slot["k"] += k
        slot["period"] += period
        for p in _PHASES:
            v = args.get(f"{p}_ms")
            if isinstance(v, (int, float)):
                slot[p] += float(v) * k  # args are per-step amortized
    if not acc:
        return ""
    lines = ["", "Attribution (per-step phase decomposition):",
             f"{'Site':<18}{'Steps':>7}{'ms/step':>10}  " +
             "".join(f"{p:>15}" for p in _PHASES)]
    for site in sorted(acc):
        slot = acc[site]
        kk = max(slot["k"], 1)
        step_ms = slot["period"] / kk
        if step_ms <= 0:
            continue
        cells = []
        for p in _PHASES:
            ms = slot[p] / kk
            cells.append(f"{ms:>7.3f} {ms / step_ms * 100:>4.0f}%  ")
        lines.append(f"{site:<18}{kk:>7}{step_ms:>10.3f}  "
                     + "".join(f"{c:>15}" for c in cells))
    lines.append("  (columns: mean ms/step and % of step period; see "
                 "docs/observability.md 'Reading an attribution report')")
    return "\n".join(lines)


#: cost-record site -> the span series whose mean duration times it
#: (a superstep span covers K iterations — and so does its FLOP count,
#: so the ratio is still per-invocation-consistent)
_SITE_SPANS = {"trainer_fused": "trainer.step",
               "superstep": "trainer.superstep"}


def render_roofline(events):
    """Per-site roofline table from ``introspect.cost`` records (one
    per registered executable; see observability/introspect.py): FLOPs,
    HBM bytes, arithmetic intensity, compute-vs-memory bound against
    the device ridge point, and achieved TFLOP/s + MFU where the dump
    also carries step spans to time the site with. Crash-proof: absent
    series -> empty string, malformed/partial records render '-' (a
    backend without cost analysis must never crash the report)."""
    by_site = {}
    for ev in events:
        if ev.get("name") != "introspect.cost":
            continue
        args = ev.get("args")
        if isinstance(args, dict) and args.get("site"):
            by_site[args["site"]] = args  # last record per site wins
    if not by_site:
        return ""
    spans = aggregate(events)

    def num(rec, key):
        v = rec.get(key)
        return float(v) if isinstance(v, (int, float)) else None

    lines = ["", "Executable roofline (XLA cost/memory analysis):",
             f"{'Site':<34}{'GFLOPs':>10}{'MiB':>9}{'AI':>8}"
             f"{'Bound':>9}{'TFLOP/s':>10}{'MFU':>8}"]
    for site in sorted(by_site):
        rec = by_site[site]
        flops = num(rec, "flops")
        nbytes = num(rec, "bytes_accessed")
        ai = num(rec, "arith_intensity")
        peak = num(rec, "peak_tflops")
        bw = num(rec, "peak_hbm_gbs")
        bound = "-"
        if ai is not None and peak and bw:
            ridge = peak * 1e12 / (bw * 1e9)
            bound = "compute" if ai >= ridge else "memory"
        achieved = mfu = None
        span = spans.get(_SITE_SPANS.get(site, ""))
        if flops is not None and span and span[0]:
            mean_s = span[1] / span[0] / 1e3  # aggregate() is ms
            if mean_s > 0:
                achieved = flops / mean_s / 1e12
                if peak:
                    mfu = achieved / peak

        def fmt(v, scale=1.0, nd=2):
            return f"{v / scale:.{nd}f}" if v is not None else "-"

        lines.append(
            f"{site:<34}{fmt(flops, 1e9, 3):>10}"
            f"{fmt(nbytes, 2 ** 20):>9}{fmt(ai, 1.0, 1):>8}"
            f"{bound:>9}{fmt(achieved, 1.0, 3):>10}"
            f"{fmt(mfu):>8}")
    return "\n".join(lines)


def render_input_pipeline(events):
    """'Input pipeline' section from the streaming-reader series:
    ``stream.batch`` spans (one per delivered batch, dur = the train
    thread's consumer wait) joined against ``trainer.step`` /
    ``trainer.superstep`` spans for the input-bound fraction, plus the
    cumulative ``stream.stats`` instants (per-shard read totals,
    decode-pool busy/wait, staging depths). Same crash-proofing
    contract as the AMP/serving sections: absent series -> empty
    string, malformed args render as '-' / count as zero."""
    batches = [ev for ev in events if ev.get("name") == "stream.batch"]
    stats = [ev for ev in events if ev.get("name") == "stream.stats"]
    if not (batches or stats):
        return ""

    def num(args, key):
        v = args.get(key) if isinstance(args, dict) else None
        return float(v) if isinstance(v, (int, float)) else None

    lines = ["", "Input pipeline:"]
    waits = [w for w in (num(ev.get("args"), "consumer_wait")
                         for ev in batches) if w is not None]
    depths = [d for d in (num(ev.get("args"), "reorder_depth")
                          for ev in batches) if d is not None]
    if batches:
        total_wait = sum(waits)
        mean_ms = total_wait / len(waits) * 1e3 if waits else 0.0
        peak_ms = max(waits) * 1e3 if waits else 0.0
        depth = (f"{sum(depths) / len(depths):.1f} avg / "
                 f"{max(depths):.0f} peak" if depths else "-")
        lines.append(
            f"  {len(batches)} batches delivered, consumer wait "
            f"{total_wait * 1e3:.1f} ms total "
            f"({mean_ms:.3f} ms/batch avg, {peak_ms:.3f} ms peak), "
            f"reorder depth {depth}")
        # join against the step spans: what fraction of train wall
        # time the device spent waiting on input
        step_us = sum(float(ev.get("dur", 0.0)) for ev in events
                      if ev.get("name") in ("trainer.step",
                                            "trainer.superstep"))
        if step_us > 0 and waits:
            frac = min(1.0, total_wait * 1e6 / step_us)
            verdict = "input-bound" if frac >= 0.15 else "saturated"
            lines.append(
                f"  input wait / step time: {frac:.1%} ({verdict} — "
                f"see mxtpu-doctor input_bound for knobs)")
    if stats:
        args = stats[-1].get("args")
        args = args if isinstance(args, dict) else {}
        busy = num(args, "decode_busy") or 0.0
        idle = num(args, "decode_wait") or 0.0
        if busy + idle > 0:
            lines.append(
                f"  decode pool: {busy:.2f} s busy / {idle:.2f} s "
                f"waiting on storage "
                f"(utilization {busy / (busy + idle):.1%})")
        raw = num(args, "depth_raw")
        lines.append(
            f"  staging depth: raw "
            f"{'-' if raw is None else f'{raw:.0f}'} / reorder "
            f"{'-' if num(args, 'depth_reorder') is None else int(args['depth_reorder'])}")
        shards = args.get("per_shard")
        if isinstance(shards, dict) and shards:
            lines.append(f"  {'Shard':<24}{'Records':>10}{'MB':>10}"
                         f"{'MB/s':>10}")
            for name in sorted(shards):
                rec = shards[name] if isinstance(shards[name], dict) \
                    else {}
                nbytes = num(rec, "bytes") or 0.0
                secs = num(rec, "seconds") or 0.0
                rate = f"{nbytes / secs / 1e6:10.1f}" if secs > 0 \
                    else f"{'-':>10}"
                lines.append(
                    f"  {str(name)[:23]:<24}"
                    f"{int(num(rec, 'records') or 0):>10}"
                    f"{nbytes / 1e6:>10.2f}{rate}")
    return "\n".join(lines)


def render_steps(events):
    """Per-step timeline of trainer.step spans, when present."""
    steps = [ev for ev in events if ev.get("name") == "trainer.step"]
    if not steps:
        return ""
    lines = ["", "Step timeline:",
             f"{'Step':>6}{'Dur (ms)':>12}{'Grad norm':>14}"]
    for ev in steps:
        args = ev.get("args") or {}
        gn = args.get("grad_norm")
        lines.append(f"{args.get('step', '?'):>6}"
                     f"{float(ev.get('dur', 0.0)) / 1e3:>12.3f}"
                     f"{(f'{gn:.4g}' if gn is not None else '-'):>14}")
    return "\n".join(lines)


def render_cluster(cluster):
    """'Cluster' section from a federation snapshot bundle: one row per
    rank — step epoch, skew behind the front-runner, snapshot age at
    bundle-generation time, series count, and the stale marker. Same
    crash-proofing contract as every other section: no bundle / no
    ranks -> empty string, malformed rank bodies render '-'."""
    if not isinstance(cluster, dict):
        return ""
    ranks = cluster.get("ranks")
    if not isinstance(ranks, dict) or not ranks:
        return ""
    stale = set()
    for r in cluster.get("stale") or []:
        try:
            stale.add(int(r))
        except (TypeError, ValueError):
            pass
    gen = cluster.get("generated_wall")
    gen = float(gen) if isinstance(gen, (int, float)) else None

    def rank_key(r):
        try:
            return (0, int(r))
        except (TypeError, ValueError):
            return (1, str(r))

    rows, steps = [], []
    for r in sorted(ranks, key=rank_key):
        snap = ranks[r] if isinstance(ranks[r], dict) else {}
        step = snap.get("step_epoch")
        step = int(step) if isinstance(step, (int, float)) else None
        if step is not None:
            steps.append(step)
        wall = snap.get("wall")
        age = (gen - float(wall)
               if gen is not None and isinstance(wall, (int, float))
               else None)
        rows.append((r, step, age, len(snap.get("metrics") or {})))
    front = max(steps) if steps else None
    lines = ["", "Cluster (federated snapshots):",
             f"{'Rank':>6}{'Step':>10}{'Skew':>8}{'Age (s)':>10}"
             f"{'Series':>9}  "]
    for r, step, age, series in rows:
        skew = (front - step
                if front is not None and step is not None else None)
        mark = "STALE" if rank_key(r)[1] in stale else ""
        lines.append(
            f"{str(r):>6}"
            f"{(str(step) if step is not None else '-'):>10}"
            f"{(str(skew) if skew is not None else '-'):>8}"
            f"{(f'{age:.1f}' if age is not None else '-'):>10}"
            f"{series:>9}  {mark}")
    if stale:
        lines.append(f"  stale ranks (> MXTPU_FEDERATION_STALE_S): "
                     f"{sorted(stale)} — marked, last series still "
                     f"exposed")
    return "\n".join(lines)


def render_anomalies(events):
    """'Anomalies' section from the watchdog's ``anomaly`` trace
    instants, aggregated by ``args.kind``. Crash-proof: absent series
    -> empty string, malformed args aggregate under '-'."""
    evs = [ev for ev in events if ev.get("name") == "anomaly"]
    if not evs:
        return ""
    by_kind = {}
    for ev in evs:
        args = ev.get("args")
        kind = str(args.get("kind", "-")) if isinstance(args, dict) \
            else "-"
        by_kind.setdefault(kind, []).append(ev)
    lines = ["", "Anomalies (watchdog):"]
    for kind in sorted(by_kind):
        kevs = by_kind[kind]
        largs = kevs[-1].get("args")
        largs = largs if isinstance(largs, dict) else {}
        detail = ", ".join(
            f"{k}={largs[k]}" for k in sorted(largs)
            if k not in ("kind",))[:120]
        lines.append(f"  {kind}: {len(kevs)} firing(s)"
                     + (f" — last: {detail}" if detail else ""))
    return "\n".join(lines)


def render_graph_contracts(root=None):
    """Static 'Graph contracts' section: what `mxtpu-lint --graph` is
    holding the tree to — pinned collective-order sites, the graph rule
    catalog, and the shared baseline size. Read from the checked-in
    tools/graph_contracts.json + tools/lint_baseline.json next to this
    script; anything missing or malformed renders as absent/'-', never
    a crash (the report must run on trimmed CI artifact dirs)."""
    import os

    if root is None:
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    try:
        with open(os.path.join(root, "tools", "graph_contracts.json"),
                  encoding="utf-8") as f:
            sites = json.load(f).get("sites", {})
        assert isinstance(sites, dict)
    except Exception:
        return ""
    n_coll = sum(len(v) for v in sites.values()
                 if isinstance(v, (list, tuple)))
    try:
        with open(os.path.join(root, "tools", "lint_baseline.json"),
                  encoding="utf-8") as f:
            entries = json.load(f).get("findings", [])
        frozen = str(len(entries))
        frozen_graph = str(sum(
            1 for e in entries
            if str(e.get("file", "")).startswith("graph:")))
    except Exception:
        frozen = frozen_graph = "-"
    try:
        if root not in sys.path:  # script runs put tools/ first, not root
            sys.path.insert(0, root)
        from tools.mxtpu_lint.graphcheck import graph_rule_names

        rules = ", ".join(graph_rule_names())
    except Exception:
        rules = "-"
    lines = ["", "Graph contracts (mxtpu-lint --graph):",
             f"  pinned sites      {len(sites)} "
             f"({n_coll} collectives): {', '.join(sorted(sites)) or '-'}",
             f"  graph rules       {rules}",
             f"  baseline frozen   {frozen} total"
             f" ({frozen_graph} graph-leg)"]
    return "\n".join(lines)


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="Aggregate a mxnet_tpu telemetry JSONL trace")
    ap.add_argument("trace", help="path to the JSONL file ('-' for stdin)")
    ap.add_argument("--cat", default=None,
                    help="only events of this category (e.g. train, "
                         "compile, comms)")
    ap.add_argument("--sort", default="total", choices=sorted(_SORTS),
                    help="sort column (default: total)")
    ap.add_argument("--ascending", action="store_true")
    ap.add_argument("--steps", action="store_true",
                    help="also print the per-step timeline")
    args = ap.parse_args(argv)

    source = sys.stdin.read() if args.trace == "-" else args.trace
    events, cluster = load_source(source)
    print(render_table(events, cat=args.cat, sort_by=args.sort,
                       ascending=args.ascending))
    amp = render_amp(events)
    if amp:
        print(amp)
    sstep = render_superstep(events)
    if sstep:
        print(sstep)
    roof = render_roofline(events)
    if roof:
        print(roof)
    attribution = render_attribution(events)
    if attribution:
        print(attribution)
    serving = render_serving(events)
    if serving:
        print(serving)
    ipipe = render_input_pipeline(events)
    if ipipe:
        print(ipipe)
    fleet = render_fleet(events)
    if fleet:
        print(fleet)
    cl = render_cluster(cluster)
    if cl:
        print(cl)
    anomalies = render_anomalies(events)
    if anomalies:
        print(anomalies)
    gc = render_graph_contracts()
    if gc:
        print(gc)
    if args.steps:
        out = render_steps(events)
        if out:
            print(out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
