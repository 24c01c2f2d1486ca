"""Plain reference of the Mistral / Mixtral decoder: the published forward
pass in straightforward ``jax.numpy`` and float32 — no kernels, no cache, no
batching, no scan, one sequence at a time.  It shares no code with
``deepspeed_tpu``.

Follows the models' public description (mistralai/Mistral-7B-v0.1 and
mistralai/Mixtral-8x7B-v0.1 ``config.json`` + their reference
implementation): pre-norm residual blocks, RMSNorm, grouped-query attention
with rotary embeddings in the half-split ("rotate_half") layout, SwiGLU MLP;
for Mixtral a softmax router over all experts, the top-k experts' outputs
weighted by their renormalised probabilities, and no token dropped.

Departures, each on purpose:
  * sliding-window attention is not applied: every sequence the benchmark
    runs is at most ``sliding_window`` tokens long, where the window mask
    and the causal mask are the same mask;
  * the load-balancing loss is the Switch form the Mixtral reference uses
    (experts x sum_e f_e * P_e), f_e the share of (token, choice) pairs
    assigned to expert e and P_e the mean router probability, both taken
    over ALL tokens of the batch (the Mixtral reference implementation
    concatenates every token's router logits before it takes them).

Weights are handed in one layer at a time as float32 (``LayerWeights``), so
a 16-layer model is checked without holding a float32 copy of it.  Matrix
multiplications run under ``jax.default_matmul_precision("highest")``: on a
TPU a float32 matmul is otherwise computed in bfloat16 passes.
"""
from __future__ import annotations

import math
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

LayerWeights = Dict[str, jnp.ndarray]
#: LayerWeights keys: attn_norm [D], wq [D, H*hd], wk [D, KV*hd],
#: wv [D, KV*hd], wo [H*hd, D], mlp_norm [D], and either
#: w_gate/w_up [D, F] + w_down [F, D] (dense) or router [D, E] +
#: w_gate/w_up [E, D, F] + w_down [E, F, D] (experts)


def rms_norm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def rope(x, theta):
    """x [S, heads, hd] at positions 0..S-1, half-split rotation."""
    seq, _, hd = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(seq, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(x, w: LayerWeights, heads: int, kv_heads: int, theta: float):
    seq, _ = x.shape
    hd = w["wq"].shape[1] // heads
    q = rope((x @ w["wq"]).reshape(seq, heads, hd), theta)
    k = rope((x @ w["wk"]).reshape(seq, kv_heads, hd), theta)
    v = (x @ w["wv"]).reshape(seq, kv_heads, hd)
    group = heads // kv_heads
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((seq, seq), bool))
    scores = jnp.where(causal[None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("hqk,khd->qhd", probs, v).reshape(seq, heads * hd)
    return out @ w["wo"]


def swiglu(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def expert_mlp(x, w: LayerWeights, top_k: int) -> Tuple[jnp.ndarray,
                                                        jnp.ndarray]:
    """Dropless top-k mixture: (output, [2, E] of this sequence's (token,
    choice) pairs per expert and summed router probabilities per expert)."""
    n_experts = w["router"].shape[1]
    probs = jax.nn.softmax(x @ w["router"], axis=-1)            # [S, E]
    top_p, top_i = jax.lax.top_k(probs, top_k)
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    out = jnp.zeros_like(x)
    for e in range(n_experts):
        weight = jnp.sum(jnp.where(top_i == e, top_p, 0.0), axis=-1)   # [S]
        y = swiglu(x, w["w_gate"][e], w["w_up"][e], w["w_down"][e])
        out = out + weight[:, None] * y
    assigned = jnp.sum(jax.nn.one_hot(top_i, n_experts), axis=(0, 1))
    return out, jnp.stack([assigned, jnp.sum(probs, axis=0)])


def load_balance(stats, tokens: int) -> float:
    """Switch load-balancing loss of one layer from the [2, E] sums of
    ``expert_mlp`` over ``tokens`` tokens."""
    assigned, prob_sum = stats
    return float(assigned.shape[0] * jnp.sum(
        assigned / jnp.sum(assigned) * prob_sum / tokens))


def layer(x, w: LayerWeights, *, heads: int, kv_heads: int, theta: float,
          eps: float, top_k: int):
    with jax.default_matmul_precision("highest"):
        x = x + attention(rms_norm(x, w["attn_norm"], eps), w, heads,
                          kv_heads, theta)
        h = rms_norm(x, w["mlp_norm"], eps)
        if "router" in w:
            y, stats = expert_mlp(h, w, top_k)
        else:
            y, stats = swiglu(h, w["w_gate"], w["w_up"], w["w_down"]), None
        return x + y, stats


def head(x, norm_scale, w_head, eps):
    with jax.default_matmul_precision("highest"):
        return rms_norm(x, norm_scale, eps) @ w_head


def next_token_loss(logits, tokens):
    """Mean cross-entropy of predicting tokens[1:] from logits[:-1]."""
    logp = jax.nn.log_softmax(logits[:-1], axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, tokens[1:, None], axis=-1))


class Reference:
    """Drives the layer function over a model whose weights arrive one
    layer at a time.  ``sizes`` are the configuration file's published keys.
    """

    def __init__(self, sizes: Dict):
        self.sizes = sizes
        kw = dict(heads=sizes["num_attention_heads"],
                  kv_heads=sizes["num_key_value_heads"],
                  theta=float(sizes["rope_theta"]),
                  eps=float(sizes["rms_norm_eps"]),
                  top_k=int(sizes.get("num_experts_per_tok", 1)))
        self._layer = jax.jit(lambda x, w: layer(x, w, **kw))
        self._head = jax.jit(
            lambda x, s, w: head(x, s, w, float(sizes["rms_norm_eps"])))
        self._loss = jax.jit(next_token_loss)

    def run(self, token_rows: List, weights: Dict, keep_logits: int = 1,
            last: Optional[int] = None) -> Tuple[List[float], List, Dict]:
        """Each row of ``token_rows`` (a 1-D int array) through the model.

        ``weights``: ``embedding`` [V, D], ``norm`` [D], ``head`` [D, V] in
        float32 and ``layers``, a list of zero-argument callables each
        returning one layer's LayerWeights (made once per layer, used for
        every row, dropped).  Returns (next-token loss per row, logits of
        the first ``keep_logits`` rows — their ``last`` positions only, if
        given — and the routing of the whole batch: ``balance``, the
        load-balancing loss averaged over the layers, which the Mixtral
        reference adds to the loss times ``router_aux_loss_coef``, and
        ``expert_loads``, per layer the (token, choice) pairs each expert
        receives).  A model without experts gives 0 and no loads.
        """
        xs = [jnp.take(weights["embedding"], row, axis=0) for row in token_rows]
        tokens = sum(int(row.shape[0]) for row in token_rows)
        per_layer = []
        for make in weights["layers"]:
            w = make()
            total = None
            for r, x in enumerate(xs):
                xs[r], stats = self._layer(x, w)
                if stats is not None:
                    total = stats if total is None else total + stats
            del w
            if total is not None:
                per_layer.append(total)
        losses, kept = [], []
        for r, (x, row) in enumerate(zip(xs, token_rows)):
            logits = self._head(x, weights["norm"], weights["head"])
            losses.append(float(self._loss(logits, row)))
            if r < keep_logits:
                kept.append(logits if last is None else logits[-last:])
        routing = {"balance": 0.0, "expert_loads": []}
        if per_layer:
            routing = {
                "balance": sum(load_balance(s, tokens) for s in per_layer)
                / len(per_layer),
                "expert_loads": [[int(n) for n in s[0]] for s in per_layer]}
        return losses, kept, routing
