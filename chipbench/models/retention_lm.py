"""Glue between the ``retention_lm`` family's configuration (a Hugging
Face ``config.json`` of the Brumby shape: Qwen3's keys, every layer a
power-retention layer) and the program's ``serving.TransformerDecoderLM``
configured for it."""

from __future__ import annotations

from ..references.retention_lm import LAYER_LEAVES

REFERENCE = "retention_lm"


def check_program():
    """Raises where the program cannot build this family, before any
    weight is drawn: a program from before it ends at once."""
    from mxnet_tpu.serving import TransformerDecoderLM

    if not hasattr(TransformerDecoderLM, "cache_spec"):
        raise RuntimeError(
            "this program's serving.TransformerDecoderLM has no retention "
            "layers (no cache_spec): the retention_lm family cannot be built")


def build_net(cfg, params, dtype):
    """The served network, holding the benchmark's own weights as they
    are: the reference keeps a layer leaf stacked on a leading axis,
    which is how the program scans its layers, so the device holds one
    copy."""
    from mxnet_tpu.serving import TransformerDecoderLM

    check_program()
    tree = {"embed": params["embed"], "head": params["head"],
            "lnf_g": params["lnf_g"],
            "layers": {k: params[k] for k in LAYER_LEAVES}}

    class Seeded(TransformerDecoderLM):
        def _init_params(self):
            return tree

    return Seeded(
        vocab_size=cfg["vocab_size"], num_layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"], num_heads=cfg["num_attention_heads"],
        kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["intermediate_size"], max_seq=cfg["max_position_embeddings"],
        dtype=dtype, norm="rmsnorm", norm_eps=cfg["rms_norm_eps"],
        positions="rope", rope_theta=cfg["rope_theta"], mlp="swiglu",
        qk_norm=True, layer_kinds="retention")


def layer_matrix_params(cfg):
    """Weights of one layer's matrix products: q, k, v, o, the gate and
    the three of the MLP."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    H, KVH = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return (2 * d * H * hd + 2 * d * KVH * hd + d * KVH
            + 3 * d * cfg["intermediate_size"])


def state_elements(cfg):
    """Numbers in one KV head's state and normaliser: the symmetric
    second power of a ``head_dim`` vector has ``hd (hd + 1) / 2``
    entries, each beside ``head_dim`` values and one of the
    normaliser. (The program's layout pads that to ``(hd / 2 + 1) hd``;
    what the mathematics needs is counted.)"""
    hd = cfg["head_dim"]
    return hd * (hd + 1) // 2 * (hd + 1)


def request_forward_flops(cfg, prompt_len, out_len):
    """Forward operations one request needs, in the state form: every
    prompt and output token but the last goes through the layers once
    (the matrices, the state's update a KV head, its read a query
    head); the vocabulary projection is made once a produced token.
    Nothing grows with the context; padding to a prompt bucket is not
    needed work."""
    through = prompt_len + out_len - 1
    heads = cfg["num_attention_heads"] + cfg["num_key_value_heads"]
    per_token = cfg["num_hidden_layers"] * (
        2 * layer_matrix_params(cfg) + 2 * state_elements(cfg) * heads)
    return through * per_token \
        + out_len * 2 * cfg["hidden_size"] * cfg["vocab_size"]


def retention_decode_calls_per_step(cfg, shapes, itemsize, counters):
    """The retention-decode calls of one decode step of the whole slot
    batch: one a layer, over the step's mean live slots. A live slot's
    states of a layer (float32, whatever the weights are) are read and
    written once, updated a KV head and read a query head."""
    steps = counters.get("decode_chunks", 0) * counters.get("chunk", 0)
    if not steps:
        return []
    live = (counters["tokens_generated"] - counters["prefills"]) / steps
    if live <= 0:
        return []
    KVH, H = cfg["num_key_value_heads"], cfg["num_attention_heads"]
    nbytes = live * 2 * KVH * state_elements(cfg) * 4
    flops = live * 2 * state_elements(cfg) * (KVH + H)
    return [("decode", flops, nbytes)] * cfg["num_hidden_layers"]
