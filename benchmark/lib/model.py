"""From a configuration file's published keys to the program's model, and
from the program's parameter tree to the plain reference's weights."""
from __future__ import annotations

from typing import Dict, List

#: --cpu-rehearsal: toy widths through the same control flow.  Wide enough
#: that most leaves clear ZeRO-3's 100k-element persistence threshold.
TOY = dict(vocab_size=1024, hidden_size=256, intermediate_size=512,
           num_attention_heads=4, num_key_value_heads=2,
           num_hidden_layers=2)


def sizes_of(config: Dict, rehearsal: bool) -> Dict:
    """The published keys as run; toy widths under --cpu-rehearsal."""
    sizes = {k: v for k, v in config.items()
             if isinstance(v, (int, float, bool)) or v is None}
    if rehearsal:
        sizes.update(TOY)
        if sizes.get("num_local_experts", 1) > 1:
            sizes["num_local_experts"] = 4
            sizes["num_hidden_layers"] = 1
    return sizes


def transformer_config(sizes: Dict, max_seq_len: int, **options):
    from deepspeed_tpu.models.transformer import TransformerConfig

    heads = sizes["num_attention_heads"]
    if sizes.get("head_dim") not in (None, sizes["hidden_size"] // heads):
        raise ValueError("head_dim != hidden_size / heads is not a shape "
                         "models/transformer.py can express")
    moe = {}
    if sizes.get("num_local_experts", 1) > 1:
        moe = dict(num_experts=sizes["num_local_experts"],
                   moe_top_k=sizes["num_experts_per_tok"],
                   moe_aux_loss_coef=sizes["router_aux_loss_coef"])
    return TransformerConfig(
        vocab_size=sizes["vocab_size"], hidden_size=sizes["hidden_size"],
        intermediate_size=sizes["intermediate_size"],
        num_layers=sizes["num_hidden_layers"], num_heads=heads,
        num_kv_heads=sizes["num_key_value_heads"],
        rope_theta=float(sizes["rope_theta"]),
        norm_eps=float(sizes["rms_norm_eps"]),
        tie_embeddings=bool(sizes["tie_word_embeddings"]),
        max_seq_len=max_seq_len, **moe, **options)


def init_params(model, seed: int, dtype):
    """Seeded random weights on the device under ONE jit, in the type they
    are used in: the draw, the scale and the cast fuse per tensor."""
    import jax

    return jax.jit(lambda k: model.init_params(k, dtype=dtype))(
        jax.random.PRNGKey(seed % (2 ** 31)))


_NAMES = {"attn_norm": ("attn_norm", "scale"), "wq": ("q_proj", "kernel"),
          "wk": ("k_proj", "kernel"), "wv": ("v_proj", "kernel"),
          "wo": ("o_proj", "kernel"), "mlp_norm": ("mlp_norm", "scale"),
          "w_gate": ("gate_proj", "kernel"), "w_up": ("up_proj", "kernel"),
          "w_down": ("down_proj", "kernel"), "router": ("router", "kernel")}


def reference_weights(params, device) -> Dict:
    """The program's parameter tree as what ``reference.decoder`` takes:
    ``embedding``, ``norm`` and ``head`` arrays and one maker per layer.
    Values are rounded to bfloat16 first (the precision the system computes
    in), moved to ``device`` in that type (half the bytes, where the
    parameters are sharded over chips), then held there in float32.  A few
    small jitted programs do the slicing and casting, whatever the depth."""
    import jax
    import jax.numpy as jnp

    def rounded(x):
        return x.astype(jnp.bfloat16)

    to_f32 = jax.jit(lambda tree: jax.tree.map(
        lambda x: x.astype(jnp.float32), tree))

    layers = params["layers"]
    names = {k: v for k, v in _NAMES.items() if v[0] in layers}
    depth = layers["q_proj"]["kernel"].shape[0]
    one_layer = jax.jit(lambda tree, i: {
        k: rounded(jax.lax.dynamic_index_in_dim(tree[a][b], i,
                                                keepdims=False))
        for k, (a, b) in names.items()})
    tied = "lm_head" not in params
    outer = jax.jit(lambda p: {
        "embedding": rounded(p["embed"]["embedding"]),
        "norm": rounded(p["norm_f"]["scale"]),
        "head": rounded(p["embed"]["embedding"].T if tied
                        else p["lm_head"]["kernel"])})

    def maker(i):
        return lambda: to_f32(jax.device_put(one_layer(layers, i), device))

    weights = to_f32(jax.device_put(outer(
        {k: params[k] for k in ("embed", "norm_f", "lm_head")
         if k in params}), device))
    weights["layers"] = [maker(i) for i in range(depth)]
    return weights


def rel_l2(a, b) -> float:
    import numpy as np

    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def logits_agreement(got, ref, tol: float) -> Dict:
    """System logits [S, V] against the reference's: rel-L2 over the whole
    row, its median over positions, and the share of positions over
    ``tol``."""
    import numpy as np

    got = np.asarray(got, np.float32)
    ref = np.asarray(ref, np.float32)
    per = np.linalg.norm(got - ref, axis=-1) \
        / np.maximum(np.linalg.norm(ref, axis=-1), 1e-30)
    return {"logits_rel_l2": rel_l2(got, ref),
            "logits_rel_l2_median": float(np.median(per)),
            "logits_share_over_tol": float(np.mean(per > tol)),
            "logits_finite": bool(np.isfinite(got).all())}
