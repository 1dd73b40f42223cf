"""Loop kind ``generation_server_experts``: ``generation_server_rows``
for a model with expert layers. The window, the comparison and the
limits are the parent kind's. Besides, the window's counters carry the
differences of the engine's ``stats()["experts"]`` (``routed_pairs``,
``experts_hit``, ``load_max``: sums over the window's decode steps and
expert layers), the constants ``expert_layers`` and ``routed_experts``,
and ``load_mean`` (``routed_pairs / routed_experts``: the pairs an
expert would take if all took the same, summed as ``load_max`` is), so
that the ``counter`` reader can give the share of the experts a step
streams and the fullest expert over the mean one.

A sequence's block table is sized for the cell's longest request (the
longest bucket and the longest answer: ``served_positions``, handed to
the family's glue in the configuration), not for the model's 131,072
positions.

The comparison has one number more, ``logit_gap_mean``: the MEAN over
the checked tokens of the gap whose largest is ``logit_gap``. A top-k
router turns rounding into a choice: where two experts' scores lie
closer than the activations' rounding, any computation below float32
(the program in bf16, the reference with bf16 operands alike) takes the
other expert now and then, and that token's logits move by an expert's
whole weight. So the LARGEST gap over some hundreds of tokens reads the
same for bf16 and for fp8 (a flip is a flip: PERF.md section 4 has the
readings), and its limit can only catch a gross fault; how OFTEN and
how far the served tokens leave the reference's first is what tells a
precision from the one below, and the mean reads that."""

from __future__ import annotations

import numpy as np

from . import generation_server, generation_server_rows

EXPERT_KEYS = ("routed_pairs", "experts_hit", "load_max")


class Loop(generation_server_rows.Loop):
    kind = "generation_server_experts"

    def setup(self):
        w = self.workload
        self.cfg = dict(self.cfg, served_positions=int(
            max(w["engine"]["buckets"])
            + w["traffic"]["output_tokens"]["max"]))
        super().setup()

    def _read(self):
        s = self.engine.stats()
        out = {k: s[k] for k in generation_server.STAT_KEYS}
        out.update({k: s["experts"][k] for k in EXPERT_KEYS})
        return out

    def _stats(self):
        """The parent kind reads this as the window opens (and keeps
        it) and as it closes: the last reading is kept here."""
        self._closed_stats = self._read()
        return self._closed_stats

    def snapshot(self):
        now = self._read()
        return {k: now[k] - self._open_stats[k] for k in now}

    def window(self, seconds):
        out = super().window(seconds)
        c = self.counters
        c.update({k: self._closed_stats[k] - self._open_stats[k]
                  for k in EXPERT_KEYS})
        c["expert_layers"] = self.model.expert_layers(self.cfg)
        c["routed_experts"] = self.cfg["n_routed_experts"]
        c["load_mean"] = c["routed_pairs"] / c["routed_experts"]
        return out

    # -- the comparison ------------------------------------------------------
    def token_gaps(self, quant=None):
        """The gap of every checked token, request by request: by how
        much the served token's logit lies under the reference's best,
        in units of that position's largest |logit| (with ``quant`` the
        token is the one the reference at that precision puts first).
        The parent kind's rows, shapes and padding."""
        import jax.numpy as jnp

        out = []
        for r in self.sample():
            p, served = r["prompt"], np.asarray(r["tokens"], np.int32)
            seq = np.concatenate([p, served])[None]
            pad = min(-len(seq[0]) % 128, self.max_seq - len(seq[0]))
            seq_p = np.pad(seq, ((0, 0), (0, pad)))
            start = len(p) - 1
            count = min(-(-len(served) // 64) * 64, seq_p.shape[1] - start)

            def rows(**kw):
                return self.ref.logits(self.params0, seq_p, self.cfg,
                                       rows=(start, count),
                                       **kw)[0][:len(served)]

            best = rows()
            ids = jnp.asarray(served) if quant is None \
                else jnp.argmax(rows(quant=quant), -1)
            chosen = jnp.take_along_axis(best, ids[:, None], -1)[:, 0]
            out.append(np.asarray(
                (best.max(-1) - chosen) / jnp.abs(best).max(-1)))
        return out

    def gaps(self, quant=None):
        """``(largest gap, tokens checked)`` as the parent kind gives
        them; the mean is kept for :meth:`check`."""
        gaps = np.concatenate(self.token_gaps(quant) or [np.zeros(0)])
        self.gap_mean = float(gaps.mean()) if gaps.size else float("inf")
        return (float(gaps.max()) if gaps.size else 0.0), int(gaps.size)

    def check(self):
        out = super().check()  # runs gaps()
        out.append(("logit_gap_mean", self.gap_mean,
                    float(self.workload["limits"]["logit_gap_mean"]),
                    f"{self.counters['checked_tokens']} tokens"))
        return out
