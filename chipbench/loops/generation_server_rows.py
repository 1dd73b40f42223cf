"""Loop kind ``generation_server_rows``: ``generation_server`` for a
model whose vocabulary times its longest request does not fit beside
its weights. The window, the counters and the limits are the parent
kind's; the comparison asks the reference for the checked rows only
(``logits(..., rows=(start, count))``: the rows that predict the served
tokens), where the parent kind takes a whole ``(1, T, V)`` array: at
8,700 tokens and 151,936 words that is 5.3 GB beside 8.4 GB of
weights. Set-up first asks the family's glue whether the program can
build it (``check_program``), so that a program from before the family
ends before it has drawn a weight."""

from __future__ import annotations

import gc

import numpy as np

from . import generation_server


class Loop(generation_server.Loop):
    kind = "generation_server_rows"

    def setup(self):
        self.model.check_program()
        # a loop that went before in this process (``tools/readings.py``
        # makes one a seed) hangs in a cycle with its context, and its
        # weights leave the device only at a collection: two sets of
        # them do not fit
        gc.collect()
        super().setup()

    def gaps(self, quant=None):
        """As the parent kind's, over the checked rows only."""
        import jax.numpy as jnp

        worst, tokens = 0.0, 0
        for r in self.sample():
            p, out = r["prompt"], np.asarray(r["tokens"], np.int32)
            seq = np.concatenate([p, out])[None]
            # few shapes: whole 128s, within the positions the model has
            pad = min(-len(seq[0]) % 128, self.max_seq - len(seq[0]))
            seq_p = np.pad(seq, ((0, 0), (0, pad)))
            # as many rows as a whole 64s holds of the answer, from the
            # row that predicts its first token
            start = len(p) - 1
            count = min(-(-len(out) // 64) * 64, seq_p.shape[1] - start)
            rows = self.ref.logits(self.params0, seq_p, self.cfg,
                                   rows=(start, count))[0][:len(out)]
            if quant is None:
                chosen_ids = jnp.asarray(out)
            else:
                low = self.ref.logits(self.params0, seq_p, self.cfg,
                                      quant=quant,
                                      rows=(start, count))[0][:len(out)]
                chosen_ids = jnp.argmax(low, -1)
            chosen = jnp.take_along_axis(rows, chosen_ids[:, None], -1)[:, 0]
            gap = (rows.max(-1) - chosen) / jnp.abs(rows).max(-1)
            worst = max(worst, float(gap.max()))
            tokens += len(out)
        return worst, tokens
