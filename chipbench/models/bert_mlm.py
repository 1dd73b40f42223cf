"""Glue between the ``bert_mlm`` family's configuration and the program:
how the program's network is built, which of its parameters is which of
the reference's leaves, and what a sample costs."""

from __future__ import annotations

from ..harness import flops as F

REFERENCE = "bert_mlm"


def build_block(cfg):
    """The program's network for this configuration (a Gluon block)."""
    from mxnet_tpu.models import bert as bert_mod

    return bert_mod.BERTModel(
        num_layers=cfg["num_hidden_layers"], units=cfg["hidden_size"],
        hidden_size=cfg["intermediate_size"],
        num_heads=cfg["num_attention_heads"], vocab_size=cfg["vocab_size"],
        token_type_vocab_size=cfg["type_vocab_size"],
        max_length=cfg["max_position_embeddings"], dropout=0.0,
        use_pooler=False, use_classifier=False, prefix="bert_")


def loss_fn(cfg):
    from mxnet_tpu import gluon

    sce = gluon.loss.SoftmaxCrossEntropyLoss()

    def mlm_loss(out, y):
        logits = out[-1] if isinstance(out, (tuple, list)) else out
        return sce(logits, y)

    return mlm_loss


def program_names(cfg):
    """reference leaf -> the program's parameter name."""
    out = {
        "word_embed": "bert_word_embed_weight",
        "token_type_embed": "bert_token_type_embed_weight",
        "position": "bert_encoder_position_weight",
        "embed_ln_g": "bert_encoder_ln_gamma",
        "embed_ln_b": "bert_encoder_ln_beta",
        "head_dense_w": "bert_decoder_dense0_weight",
        "head_dense_b": "bert_decoder_dense0_bias",
        "head_ln_g": "bert_decoder_layernorm0_gamma",
        "head_ln_b": "bert_decoder_layernorm0_beta",
        "head_out_w": "bert_decoder_dense1_weight",
        "head_out_b": "bert_decoder_dense1_bias",
    }
    proj = {"q": "attn_query", "k": "attn_key", "v": "attn_value",
            "o": "attn_out", "ffn1": "ffn_ffn_1", "ffn2": "ffn_ffn_2"}
    for i in range(cfg["num_hidden_layers"]):
        p, q = f"layer{i}_", f"bert_encoder_cells_transformer{i}_"
        for a, b in proj.items():
            out[p + a + "_w"] = q + b + "_weight"
            out[p + a + "_b"] = q + b + "_bias"
        for ln in ("ln1", "ln2"):
            out[p + ln + "_g"] = q + ln + "_gamma"
            out[p + ln + "_b"] = q + ln + "_beta"
    return out


def sample_shape(cfg, shapes):
    return (int(shapes["seq"]),)


def label_shape(cfg, shapes):
    return (int(shapes["seq"]),)


def label_range(cfg):
    return cfg["vocab_size"]


def train_flops_per_sample(cfg, shapes):
    T = int(shapes["seq"])
    fwd = F.transformer_forward_flops_per_token(
        layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        d_ff=cfg["intermediate_size"], vocab=cfg["vocab_size"], context=T,
        head_extra_dense=True)
    return 3 * fwd * T


def attention_calls_per_step(cfg, shapes, itemsize, counters=None):
    """[(kind, flops, bytes)] for the flash-attention calls of one
    training step: a forward, a dq and a dk/dv call a layer."""
    H = cfg["num_attention_heads"]
    kw = dict(batch=int(shapes["batch"]), heads=H, q_len=int(shapes["seq"]),
              k_len=int(shapes["seq"]), head_dim=cfg["hidden_size"] // H,
              itemsize=itemsize)
    out = []
    for _ in range(cfg["num_hidden_layers"]):
        out += [("fwd",) + F.attention_call_cost(passes=2, **kw),
                ("bwd_dq",) + F.attention_call_cost(passes=3, **kw),
                ("bwd_dkv",) + F.attention_call_cost(passes=4, **kw)]
    return out
