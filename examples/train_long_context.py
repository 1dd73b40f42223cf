"""Long-context training with sliding-window attention.

Two ways to go past quadratic attention, both in this repo:

1. ``sliding_window`` (this script): Mistral-style local attention — the
   banded Pallas kernels skip out-of-band block compute, O(T*W) FLOPs.
   One chip handles 32k tokens.
2. Ring attention (``parallel/ring_attention.py``): exact full attention
   with the SEQUENCE sharded over a mesh axis and k/v blocks rotating
   over ICI — for when the context must be global.

Run:  python examples/train_long_context.py [seq_len] [window]
(defaults 2048/256; small enough for the CPU path, TPU picks up the
Pallas kernels automatically).
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon
from mxnet_tpu.models.llama import LlamaModel

SEQ = int(sys.argv[1]) if len(sys.argv) > 1 else 2048
WINDOW = int(sys.argv[2]) if len(sys.argv) > 2 else 256
VOCAB = 256


def make_batch(rng, batch=2):
    """Synthetic copy-task data with long-range structure: the sequence
    is periodic with period < window, so local attention suffices and
    the loss floor is near zero."""
    base = rng.randint(0, VOCAB, (batch, WINDOW // 2))
    reps = SEQ // base.shape[1] + 2
    seq = np.tile(base, (1, reps))[:, :SEQ + 1].astype(np.float32)
    return mx.nd.array(seq[:, :-1]), mx.nd.array(seq[:, 1:])


def main():
    rng = np.random.RandomState(0)
    net = LlamaModel(vocab_size=VOCAB, num_layers=2, units=64,
                     intermediate=128, num_heads=4, num_kv_heads=2,
                     sliding_window=WINDOW)
    net.initialize(init=mx.initializer.Xavier())
    trainer = gluon.Trainer(net.collect_params(), "adam",
                            {"learning_rate": 3e-3})
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    print(f"seq_len={SEQ} window={WINDOW} "
          f"(attention FLOPs ~{WINDOW / SEQ:.1%} of full causal)")
    x, y = make_batch(rng)  # one long batch; the model fits it quickly
    first = None
    for step in range(40):
        with autograd.record():
            logits = net(x)
            loss = loss_fn(logits.reshape((-1, VOCAB)),
                           y.reshape((-1,))).mean()
        loss.backward()
        trainer.step(1)
        v = float(loss.asnumpy())
        first = first if first is not None else v
        if step % 5 == 0 or step == 39:
            print(f"step {step:3d}  loss {v:.4f}")
    assert v < first, "loss did not improve"


if __name__ == "__main__":
    main()
