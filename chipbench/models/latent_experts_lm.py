"""Glue between the ``latent_experts_lm`` family's configuration (a
Hugging Face ``config.json`` of the DeepSeek-V3 shape: latent attention,
leading dense layers, then sigmoid-routed experts with a shared one) and
the program's ``serving.TransformerDecoderLM`` configured for it."""

from __future__ import annotations

from ..references.latent_experts_lm import layer_leaves

REFERENCE = "latent_experts_lm"


def check_program():
    """Raises where the program cannot build this family, before any
    weight is drawn: a program from before it ends at once."""
    from mxnet_tpu.serving import decoder

    if "latent" not in getattr(decoder, "KINDS", ()):
        raise RuntimeError(
            "this program's serving.TransformerDecoderLM has no latent "
            "layer kind: the latent_experts_lm family cannot be built")


def expert_layers(cfg):
    return cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]


def build_net(cfg, params, dtype):
    """The served network, holding the benchmark's own weights as they
    are: the reference keeps every layer's leaves on their own (an
    expert layer's experts stacked on the leaf's first axis), which is
    how the program runs them, so the device holds one copy.
    ``served_positions`` (the loop kind's: the longest bucket and the
    longest answer) sizes a sequence's block table; the model's own
    131,072 positions would make it 8,192 blocks a slot."""
    from mxnet_tpu.serving import TransformerDecoderLM

    check_program()
    dense, moe = cfg["first_k_dense_replace"], expert_layers(cfg)
    tree = {"embed": params["embed"], "head": params["head"],
            "lnf_g": params["lnf_g"],
            "layers": [{k: params[f"layer{i}.{k}"]
                        for k in layer_leaves(cfg, i)}
                       for i in range(cfg["num_hidden_layers"])]}

    class Seeded(TransformerDecoderLM):
        def _init_params(self):
            return tree

    return Seeded(
        vocab_size=cfg["vocab_size"], num_layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"], num_heads=cfg["num_attention_heads"],
        kv_heads=cfg["num_key_value_heads"], d_ff=cfg["intermediate_size"],
        max_seq=cfg.get("served_positions", cfg["max_position_embeddings"]),
        dtype=dtype, norm="rmsnorm", norm_eps=cfg["rms_norm_eps"],
        positions="rope", rope_theta=cfg["rope_theta"],
        mlp=["swiglu"] * dense + ["experts"] * moe, layer_kinds="latent",
        rope_interleave=cfg["rope_interleave"],
        **{k: cfg[k] for k in (
            "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
            "qk_rope_head_dim", "v_head_dim", "n_routed_experts",
            "num_experts_per_tok", "moe_intermediate_size",
            "n_shared_experts", "routed_scaling_factor")})


def attention_matrix_params(cfg):
    """Weights of one layer's attention products: the queries' two, the
    latent's two, the output's."""
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    rank, vd = cfg["kv_lora_rank"], cfg["v_head_dim"]
    return (d * cfg["q_lora_rank"] + cfg["q_lora_rank"] * H * (nope + rope)
            + d * (rank + rope) + rank * H * (nope + vd) + H * vd * d)


def expert_params(cfg):
    """Weights of one expert's three matrices."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def request_forward_flops(cfg, prompt_len, out_len):
    """Forward operations one request needs. Every prompt and output
    token but the last goes through the layers once: the attention's
    matrices (``W_kvb`` once a token in either form: it expands the
    latent in prefill and carries the query in and the result out in
    decode), the dense layers' MLP, and in an expert layer the router,
    ``num_experts_per_tok`` experts and the shared ones. A prompt token
    attends in the expanded form (a head ``qk_head_dim`` for the score
    and ``v_head_dim`` for the sum, over what precedes it and itself),
    a decode token in the absorbed form over its context's latent rows
    (``kv_lora_rank + qk_rope_head_dim`` for the score, ``kv_lora_rank``
    for the sum). The vocabulary projection is made once a produced
    token. Padding to a prompt bucket is not needed work."""
    through = prompt_len + out_len - 1
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    dense, moe = cfg["first_k_dense_replace"], expert_layers(cfg)
    rank, rope = cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]
    per_token = 2 * (
        cfg["num_hidden_layers"] * attention_matrix_params(cfg)
        + dense * 3 * d * cfg["intermediate_size"]
        + moe * (d * cfg["n_routed_experts"] + expert_params(cfg) * (
            cfg["num_experts_per_tok"] + cfg["n_shared_experts"])))
    expanded = 2 * H * (cfg["qk_nope_head_dim"] + rope + cfg["v_head_dim"])
    absorbed = 2 * H * (2 * rank + rope)
    pairs_prompt = prompt_len * (prompt_len + 1) // 2
    pairs_decode = sum(range(prompt_len + 1, through + 1))
    attend = cfg["num_hidden_layers"] * (expanded * pairs_prompt
                                         + absorbed * pairs_decode)
    return through * per_token + attend + out_len * 2 * d * cfg["vocab_size"]


def latent_decode_calls_per_step(cfg, shapes, itemsize, counters):
    """The latent-decode calls of one decode step of the whole slot
    batch: one a layer, over the live contexts the step attends to. A
    live token's row (``kv_lora_rank + qk_rope_head_dim`` numbers, what
    the algorithm needs: the pool's lane tiles pad it to 640) is read
    once; every head scores against all of it and sums its latent
    part."""
    ctx = counters.get("mean_live_context_tokens")
    if not ctx:
        return []
    rank, rope = cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]
    nbytes = ctx * (rank + rope) * itemsize
    flops = ctx * 2 * cfg["num_attention_heads"] * (2 * rank + rope)
    return [("decode", flops, nbytes)] * cfg["num_hidden_layers"]


def experts_calls_per_step(cfg, shapes, itemsize, counters):
    """The expert layers' grouped products of one decode step: three a
    layer, counted as one call. Bytes: the three matrices of every
    distinct expert the step's live tokens hit (the counter
    ``experts_hit``, a mean a step a layer); operations: the step's
    routed pairs through them."""
    units = (counters.get("decode_chunks", 0) * counters.get("chunk", 0)
             * counters.get("expert_layers", 0))
    if not units or not counters.get("experts_hit"):
        return []
    nbytes = counters["experts_hit"] / units * expert_params(cfg) * itemsize
    flops = counters["routed_pairs"] / units * 2 * expert_params(cfg)
    return [("experts", flops, nbytes)] * counters["expert_layers"]
