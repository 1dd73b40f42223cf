"""Loop kind ``spmd_step``: ``parallel.SPMDTrainStep`` called once a step
on a ring of seeded batches that live on the device.

Parameters (the cell's file): ``batch``, ``shapes`` (what the family's
glue needs: ``seq``), ``dtype``, ``optimizer``, ``optimizer_params``,
``lr``, ``step_options`` (further keywords of ``SPMDTrainStep``, such as
``{"multi_precision": true}``), ``ring``, ``warmup_steps``,
``max_ahead``. One chip: a mesh comes with the first cell across chips.
"""

from __future__ import annotations

from ..harness import train_check
from . import common


class Loop(common.TrainLoop):
    kind = "spmd_step"

    def build_program(self):
        import mxnet_tpu as mx
        from mxnet_tpu import autograd, parallel

        w = self.workload
        mx.random.seed(self.seed % (2 ** 31))
        net = self.model.build_block(self.cfg)
        net.initialize(init=mx.initializer.Zero())
        net.cast(w["dtype"])
        # shapes resolve in one eager row; then the benchmark's own
        # weights go in, before the step copies them into its state
        with autograd.predict_mode():
            net(mx.nd.NDArray(self.ring[0][0][0:1]))
        params = net.collect_params()
        for leaf, name in self.names.items():
            params[name].set_data(self.params0[leaf])
        self.step = parallel.SPMDTrainStep(
            net, self.model.loss_fn(self.cfg), w["optimizer"],
            dict(w.get("optimizer_params") or {}), mesh=None,
            **dict(w.get("step_options") or {}))

    def one_step(self, i):
        x, y = self.ring[i % len(self.ring)]
        return self.step(x, y, lr=self.lr, sync=False)

    def wait_for(self, out):
        import jax

        jax.block_until_ready(out)

    def _state_by_leaf(self, what):
        """The program's weights or first-moment leaves, by the
        reference's leaf names. With float32 master copies (state leaf
        0 of a low-precision parameter) the weights are the masters."""
        idx = {n: i for i, n in enumerate(self.step._names)}
        params, opt_states = self.step._state
        out = {}
        for leaf, name in self.names.items():
            i = idx[name]
            kept = 1 if self.has_master else 0
            if what == "moment":
                out[leaf] = opt_states[i][kept]
            else:
                out[leaf] = opt_states[i][0] if kept else params[i]
        return out

    def first_moment_norms(self):
        return train_check.tree_norms(self._state_by_leaf("moment"))

    def change_norms(self):
        return train_check.tree_change_norms(self._state_by_leaf("weights"),
                                             self.params0)

    def release_program(self):
        self.step._state = None
        self.step._compiled = None
        self.step = None
