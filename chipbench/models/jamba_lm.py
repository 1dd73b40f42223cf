"""Glue between the ``jamba_lm`` family's configuration (a Hugging Face
``config.json`` of ``model_type`` jamba: Mamba-1 layers with an
attention layer every ``attn_layer_period``, here dense) and the
program's ``serving.TransformerDecoderLM`` configured for it."""

from __future__ import annotations

from ..references import jamba_lm as ref

REFERENCE = "jamba_lm"


def check_program():
    """Raises where the program cannot build this family, before any
    weight is drawn: a program from before it ends at once."""
    from mxnet_tpu.serving import decoder

    if "mamba" not in decoder.KINDS:
        raise RuntimeError(
            "this program's serving.TransformerDecoderLM has no Mamba "
            "layers: the jamba_lm family cannot be built")


def build_net(cfg, params, dtype):
    """The served network, holding the benchmark's own weights as they
    are: the reference keeps a run of Mamba layers stacked on a leading
    axis, which is how the program scans them, and the head is the
    embedding, so the device holds one copy."""
    from mxnet_tpu.serving import TransformerDecoderLM

    check_program()
    tree = {"embed": params["embed"], "lnf_g": params["lnf_g"],
            "layers": params["layers"]}

    class Seeded(TransformerDecoderLM):
        def _init_params(self):
            return tree

    return Seeded(
        vocab_size=cfg["vocab_size"], num_layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"], num_heads=cfg["num_attention_heads"],
        kv_heads=cfg["num_key_value_heads"], d_ff=cfg["intermediate_size"],
        max_seq=cfg["served_positions"], dtype=dtype, norm="rmsnorm",
        norm_eps=cfg["rms_norm_eps"], positions="none", mlp="swiglu",
        layer_kinds=ref.layer_kinds(cfg), expand=cfg["mamba_expand"],
        d_state=cfg["mamba_d_state"], d_conv=cfg["mamba_d_conv"],
        dt_rank=cfg["mamba_dt_rank"],
        tie_head=cfg["tie_word_embeddings"])


def _matrix_params(cfg, kind):
    """Weights of one layer's matrix products: the mixer's and the
    gated MLP's three."""
    z = ref.sizes(cfg)
    d = z["d"]
    mlp = 3 * d * z["ff"]
    if kind == "attention":
        return 2 * d * z["H"] * z["hd"] + 2 * d * z["KVH"] * z["hd"] + mlp
    Di, N, R = z["Di"], z["N"], z["R"]
    return d * 2 * Di + Di * (R + 2 * N) + R * Di + Di * d + mlp


def _scan_flops(cfg):
    """A Mamba layer's operations a token outside its matrices: the
    convolution's taps and, a state element each, the decay's product,
    the input's outer product and sum, and the read-out's."""
    z = ref.sizes(cfg)
    return 2 * z["K"] * z["Di"] + 6 * z["N"] * z["Di"]


def request_forward_flops(cfg, prompt_len, out_len):
    """Forward operations one request needs: every prompt and output
    token but the last goes through the layers once (the matrices, a
    Mamba layer's scan, an attention layer's scores and weighted sum
    over what precedes it and itself); the vocabulary projection is
    made once a produced token. Padding to a bucket is not needed
    work."""
    z = ref.sizes(cfg)
    through = prompt_len + out_len - 1
    kinds = ref.layer_kinds(cfg)
    per_token = sum(2 * _matrix_params(cfg, k) for k in kinds) \
        + kinds.count("mamba") * _scan_flops(cfg)
    attend = kinds.count("attention") * 4 * z["H"] * z["hd"] \
        * through * (through + 1) // 2
    return through * per_token + attend + out_len * 2 * z["d"] * z["V"]


def mamba_decode_calls_per_step(cfg, shapes, itemsize, counters):
    """The Mamba-decode calls of one decode step of the whole slot
    batch: two a Mamba layer (the window's, then the state's), over
    the step's mean live slots. A live slot's window and state of a
    layer (float32, whatever the weights are) are read and written once
    each, and its rows (float32) go in and out: to the first call its
    input, from it the convolved row; to the second that row, ``dt``,
    ``B`` and ``C``, from it ``y``."""
    steps = counters.get("decode_chunks", 0) * counters.get("chunk", 0)
    if not steps:
        return []
    live = (counters["tokens_generated"] - counters["prefills"]) / steps
    if live <= 0:
        return []
    z = ref.sizes(cfg)
    Di, N, K = z["Di"], z["N"], z["K"]
    conv = live * (2 * (K - 1) * Di + 2 * Di) * 4
    scan = live * (2 * N * Di + 3 * Di + 2 * N) * 4
    conv_flops = live * (2 * K + 4) * Di
    scan_flops = live * 6 * N * Di
    return [("conv", conv_flops, conv), ("scan", scan_flops, scan)] \
        * ref.layer_kinds(cfg).count("mamba")
