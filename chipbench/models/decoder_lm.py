"""Glue between the ``decoder_lm`` family's configuration and the
program's ``serving.TransformerDecoderLM``."""

from __future__ import annotations

from ..harness import flops as F

REFERENCE = "decoder_lm"


def build_net(cfg, params, dtype):
    """The served network, holding the benchmark's own weights (the
    class would otherwise draw its own on the host from numpy)."""
    from mxnet_tpu.serving import TransformerDecoderLM

    tree = {
        "embed": params["embed"], "pos": params["pos"],
        "lnf_g": params["lnf_g"], "lnf_b": params["lnf_b"],
        "head": params["head"],
        "layers": [{k: params[f"layer{i}_{k}"] for k in (
            "ln1_g", "ln1_b", "wq", "wk", "wv", "wo", "ln2_g", "ln2_b",
            "w1", "b1", "w2", "b2")} for i in range(cfg["n_layer"])],
    }

    class Seeded(TransformerDecoderLM):
        def _init_params(self):
            return tree

    return Seeded(vocab_size=cfg["vocab_size"], num_layers=cfg["n_layer"],
                  d_model=cfg["n_embd"], num_heads=cfg["n_head"],
                  d_ff=cfg.get("n_inner") or 4 * cfg["n_embd"],
                  max_seq=cfg["n_positions"], dtype=dtype)


def request_forward_flops(cfg, prompt_len, out_len):
    """Forward operations one request needs: every prompt and output
    token but the last goes through the layers once, attending to what
    precedes it and itself; the vocabulary projection is made once a
    produced token. Padding to a prompt bucket is not needed work."""
    through = prompt_len + out_len - 1
    d = cfg["n_embd"]
    per_token = F.transformer_forward_flops_per_token(
        layers=cfg["n_layer"], d_model=d,
        d_ff=cfg.get("n_inner") or 4 * d, vocab=0, context=0)
    attend = cfg["n_layer"] * 4 * d * through * (through + 1) // 2
    return through * per_token + attend + out_len * 2 * d * cfg["vocab_size"]


def paged_decode_calls_per_step(cfg, shapes, itemsize, counters):
    """The paged-decode calls of one decode step of the whole slot
    batch: one a layer, over the live contexts the step attends to."""
    ctx = counters.get("mean_live_context_tokens")
    if not ctx:
        return []
    H = cfg["n_head"]
    cost = F.paged_decode_call_cost(
        context_tokens=ctx, kv_heads=H, heads=H,
        head_dim=cfg["n_embd"] // H, itemsize=itemsize)
    return [("decode",) + cost] * cfg["n_layer"]
