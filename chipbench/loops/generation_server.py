"""Loop kind ``generation_server``: ``serving.GenerationEngine`` under an
open loop of greedy requests.

Parameters (the cell's file): ``dtype``, ``engine`` (``buckets``,
``slots``, ``chunk``, ``cache_block_size``, ``cache_blocks``),
``traffic`` (see ``harness/traffic.py``), ``check`` (``requests``: how
many finished requests the reference follows; the longest is always one
of them), ``drain_s``, ``limits``.

One thread, the caller's, sends every request at the time it is due and
reads nothing back until the window has closed; the engine's own
scheduler thread is the system under test.
"""

from __future__ import annotations

import time

import numpy as np

from ..harness import traffic as T
from . import common

STAT_KEYS = ("tokens_generated", "prefills", "decode_chunks", "failed",
             "shed", "timeouts", "requests_ok")


class Loop:
    kind = "generation_server"

    def __init__(self, ctx):
        self.ctx = ctx
        self.cfg, self.workload, self.seed = ctx.cfg, ctx.workload, ctx.seed
        self.model, self.ref = common.load_family(self.cfg)
        self.counters = {}

    def setup(self):
        from mxnet_tpu.serving import GenerationEngine

        w = self.workload
        e = w["engine"]
        self.params0 = self.ref.init_params(self.cfg, self.seed, w["dtype"])
        net = self.model.build_net(self.cfg, self.params0, w["dtype"])
        self.engine = GenerationEngine(
            net, list(e["buckets"]), slots=int(e["slots"]),
            chunk=int(e["chunk"]), cache_blocks=int(e["cache_blocks"]),
            cache_block_size=int(e["cache_block_size"]),
            seed=self.seed % (2 ** 31), name="chipbench")
        self.max_seq = self.engine.max_seq
        self.counters.update(slots=int(e["slots"]), chunk=int(e["chunk"]))

    def _stats(self):
        s = self.engine.stats()
        return {k: s[k] for k in STAT_KEYS}

    def snapshot(self):
        """Counters since the window opened (for the traced part)."""
        now = self._stats()
        return {k: now[k] - self._open_stats[k] for k in STAT_KEYS}

    def window(self, seconds):
        w = self.workload
        tr = w["traffic"]
        ramp = float(tr.get("ramp_s", 0.0))
        reqs = T.make_requests(tr, self.cfg["vocab_size"], seconds, self.seed)
        span, tick = self.ctx.span, self.ctx.tick
        cache = self.engine.cache
        sent = []  # (due_abs, future or error, prompt, n_out, late_s, mine)
        blocks = []  # the pool's blocks in use, read at each submit
        start = time.perf_counter()
        t_open, t_close = start + ramp, start + ramp + seconds
        opened = False
        for due, prompt, n_out, in_window in reqs:
            if in_window and not opened:
                self._sleep_until(t_open)
                self._open_stats = self._stats()
                t_open = time.perf_counter()
                opened = True
                self.ctx.open_window(self.snapshot)
            with span("wait_arrival"):
                self._sleep_until(start + due)
            with span("submit"):
                try:
                    fut = self.engine.submit(prompt, max_new_tokens=n_out,
                                             greedy=True)
                except Exception as err:  # refused: counts as failed
                    fut = err
            sent.append((start + due, fut, prompt, n_out,
                         time.perf_counter() - (start + due), in_window))
            if in_window:
                blocks.append(cache.blocks_used())
            tick()
        with span("wait_close"):
            self._sleep_until(t_close)
        close_stats = self._stats()
        t_close = time.perf_counter()
        self.ctx.close_window()
        in_window = {k: close_stats[k] - self._open_stats[k]
                     for k in STAT_KEYS}
        self.counters.update(
            kv_blocks=cache.num_blocks,
            kv_blocks_in_use_max=max(blocks), kv_blocks_in_use_mean=float(
                np.mean(blocks)))
        # every request is followed to its end: those due in the window
        # are the run's, the ramp's only say what the window's steps read
        give_up = t_close + float(w.get("drain_s", 60.0))
        self.results, self.ramp_results = [], []
        for due, fut, prompt, n_out, late, mine in sent:
            rec = {"due": due, "prompt": prompt, "n_out": n_out,
                   "late_s": late, "tokens": None, "error": None}
            if isinstance(fut, Exception):
                rec["error"] = repr(fut)
            else:
                try:
                    rec["tokens"] = fut.result(
                        timeout=max(0.0, give_up - time.perf_counter()))
                    rec["t_first"], rec["t_last"] = fut.token_times()
                except Exception as err:  # never came, or failed inside
                    rec["error"] = repr(err)
            rec["seen"] = time.perf_counter()
            (self.results if mine else self.ramp_results).append(rec)
        self._reduce(in_window, t_open, t_close)
        return t_open, t_close

    @staticmethod
    def _sleep_until(t):
        while True:
            left = t - time.perf_counter()
            if left <= 0:
                return
            time.sleep(min(left, 0.05))

    def _reduce(self, in_window, t_open, t_close):
        window_s = t_close - t_open
        ok = [r for r in self.results if r["error"] is None]
        # a request that failed, was shed or never came counts as the
        # worst of the window, or as long as it was waited for if longer
        waited = [(r["seen"] - r["due"]) * 1e3 for r in self.results
                  if r["error"] is not None]
        worst_ttft = max([(r["t_first"] - r["due"]) * 1e3 for r in ok]
                         + waited + [0.0])
        ttft = [(r["t_first"] - r["due"]) * 1e3 if r["error"] is None
                else worst_ttft for r in self.results]
        worst_tpot = max([(r["t_last"] - r["t_first"]) * 1e3
                          / max(1, len(r["tokens"]) - 1) for r in ok] or [0.0])
        tpot = [(r["t_last"] - r["t_first"]) * 1e3 / (len(r["tokens"]) - 1)
                if r["error"] is None else worst_tpot
                for r in self.results
                if r["error"] is not None or len(r["tokens"]) > 1]
        c = self.counters
        c.update(in_window)
        c["window_s"] = window_s
        c["tokens_per_s"] = in_window["tokens_generated"] / window_s
        c["ttft_ms_p95"] = T.percentile(ttft, 95) if ttft else None
        c["tpot_ms_p95"] = T.percentile(tpot, 95) if tpot else None
        c["ttft_ms_p50"] = T.percentile(ttft, 50) if ttft else None
        c["tpot_ms_p50"] = T.percentile(tpot, 50) if tpot else None
        c["tpot_ms_mean"] = float(np.mean(tpot)) if tpot else None
        c["requests_due"] = len(self.results)
        c["generator_late_ms_p95"] = T.percentile(
            [r["late_s"] * 1e3 for r in self.results], 95) \
            if self.results else None
        # what the step_mfu needs: exact forward operations of the
        # finished requests
        c["model_flops_per_s"] = sum(
            self.model.request_forward_flops(
                self.cfg, len(r["prompt"]), len(r["tokens"]))
            for r in ok) / max(1e-9, window_s)
        # what the decode kernel's roofline needs: the tokens of context
        # that the window's decode steps attended to, counted request by
        # request (the ramp's too): step j of a request attends to its
        # prompt and j tokens, and falls (there are no stamps a token)
        # evenly between its first and its last token
        reads = 0
        for r in self.ramp_results + self.results:
            if r["error"] is not None or len(r["tokens"]) < 2:
                continue
            j = np.arange(1, len(r["tokens"]))
            at = r["t_first"] + (r["t_last"] - r["t_first"]) * j / j[-1]
            inside = (at >= t_open) & (at <= t_close)
            reads += int((len(r["prompt"]) + j)[inside].sum())
        batch_steps = in_window["decode_chunks"] * c["chunk"]
        if batch_steps and reads:
            c["context_tokens_read"] = reads
            c["mean_live_context_tokens"] = reads / batch_steps

    def outcome(self):
        failed = sum(1 for r in self.results if r["error"] is not None)
        return len(self.results), failed

    def release(self):
        # past the knee work may be left over: nothing waits for it
        if self.engine.queue_depth() or self.engine.active_slots():
            self.engine.kill()
        else:
            self.engine.close()
        self.engine = None

    # -- the comparison ---------------------------------------------------------
    def sample(self):
        """Finished requests the reference follows: the longest and, from
        the seed, ``check.requests - 1`` others."""
        ok = [r for r in self.results if r["error"] is None]
        if not ok:
            return []
        k = int(self.workload.get("check", {}).get("requests", 6))
        longest = max(range(len(ok)),
                      key=lambda i: len(ok[i]["prompt"]) + len(ok[i]["tokens"]))
        rng = np.random.RandomState(self.seed % (2 ** 32))
        rest = [i for i in rng.permutation(len(ok)) if i != longest]
        return [ok[i] for i in [longest] + rest[:k - 1]]

    def gaps(self, quant=None):
        """Widest gap by which a served token's logit lies under the
        reference's best, over every served token of the sample, in
        units of that position's largest |logit|. With ``quant`` the
        token is not the served one but the one a reference computed at
        that lower precision puts first (the control)."""
        import jax.numpy as jnp

        worst, tokens = 0.0, 0
        for r in self.sample():
            p, out = r["prompt"], np.asarray(r["tokens"], np.int32)
            seq = np.concatenate([p, out])[None]
            # few shapes: whole 128s, within the positions the model has
            pad = min(-len(seq[0]) % 128, self.max_seq - len(seq[0]))
            seq_p = np.pad(seq, ((0, 0), (0, pad)))
            logits = self.ref.logits(self.params0, seq_p, self.cfg)[0]
            at = slice(len(p) - 1, len(p) - 1 + len(out))
            rows = logits[at]
            if quant is None:
                chosen_ids = jnp.asarray(out)
            else:
                low = self.ref.logits(self.params0, seq_p, self.cfg,
                                      quant=quant)[0][at]
                chosen_ids = jnp.argmax(low, -1)
            chosen = jnp.take_along_axis(rows, chosen_ids[:, None], -1)[:, 0]
            gap = (rows.max(-1) - chosen) / jnp.abs(rows).max(-1)
            worst = max(worst, float(gap.max()))
            tokens += len(out)
        return worst, tokens

    def check(self):
        limits = self.workload["limits"]
        worst, tokens = self.gaps()
        self.counters["checked_tokens"] = tokens
        out = [("logit_gap", worst if tokens else float("inf"),
                float(limits["logit_gap"]), f"{tokens} tokens")]
        short = [r for r in self.results if r["error"] is None
                 and len(r["tokens"]) != r["n_out"]]
        out.append(("short_answers", float(len(short)), 0.0,
                    f"{len(self.results)} requests"))
        return out
