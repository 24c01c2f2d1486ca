"""Plain reference of JoyAI-LLM-Flash on the TRAINING path: the forward of
the layers held, both loss terms, both heads' logits, the per-layer expert
loads, the selection bias the ``noaux_tc`` rule gives after the step, and
gradients by ``jax.grad``.

``jax.numpy`` in float32 under ``jax.default_matmul_precision("highest")``,
one sequence at a time, no kernels, no cache, no sort by expert: attention is
the dense causal softmax of ``q·kᵀ / sqrt(192)``, an expert layer a loop
(``lax.scan``, so that the program holds one expert's body, not sixteen) over
the experts held, each on EVERY token under a dense mask of weights.  It imports nothing of ``deepspeed_tpu``
(not ``models/joyai_flash.py``, not ``moe/dropless.py``).

Weights (a plain dict, any float dtype: every use casts to float32 first, so
bf16-rounded weights may stay in bf16 on the device):

    embedding [V, D], head [D, V], norm [D],
    layers: [ {attn_norm, q_a [D, Rq], q_a_norm, q_b [Rq, H·192],
               kv_a [D, 512 + 64], kv_a_norm, kv_b [512, H·(128 + 128)],
               o [H·128, D], mlp_norm,
               then  w_gate, w_up [D, F], w_down [F, D]          (dense)
               or    router [D, E_all], experts {gate, up [E, D, F],
                     down [E, F, D]}, shared {gate, up, down}     (experts)
              } ... ],
    mtp: {enorm, hnorm, eh_proj [2D, D], layer {an expert layer}, norm}

Per token, pre-norm residual: ``x += Attn(RMSNorm(x))``, ``x +=
FFN(RMSNorm(x))``.  MLA in its expanded form (DeepSeek-V3, arXiv:2412.19437
section 2.1): ``c_q = RMSNorm(h W_qa)``, ``q = c_q W_qb`` a head ``[128 no
position | 64 rotary]``; ``[c_kv | k_r] = h W_kva``, ``c_kv = RMSNorm(
c_kv)``, ``[k_nope | v] = c_kv W_kvb`` a head ``[128 | 128]``; rotary on
``q``'s last 64 and on ``k_r``, which every head shares.  Experts:
``s = sigmoid(h W_r)``, the top 8 of ``s + b``, weights ``s`` at those
WITHOUT ``b``, over their sum, times 2.5; plus the shared expert.  The MTP
module (section 2.2): ``h'_i = W_eh [RMSNorm(Emb(t_{i+1})) ; RMSNorm(h_i)]``
with ``h_i`` the main model's output after its final norm, one expert layer,
a final norm, the shared embedding and head; it predicts ``t_{i+2}``.

Departures from the published description, each on purpose:

* A CHIP'S SHARE (model-configs guide section 4): ``experts`` holds ``E <
  E_all`` experts, ids ``offset .. offset + E`` of the router's ``E_all``
  outputs; what the others would add is left out, and that partial sum goes
  on.  The vocabulary is a slice: logits and both losses are over it.
* Rotary pairs are NEIGHBOURS (``rope_interleave``): ``(x[2i], x[2i+1])``
  turns by ``pos · theta^(-2i/64)`` in place.  HF's code moves the pairs
  apart first and rotates halves: the same q·k for every pair of positions.
* No YaRN and no ``mscale``: ``rope_scaling`` is null in the config.
* The MTP module runs over all ``S`` positions; the token after the last is
  id 0 (Megatron's rolled ids).  That position is in neither mean but its
  (token, choice) pairs are in the MTP layer's loads.
* ``loss = CE(main, t_{i+1}) + mtp_loss_weight · CE(mtp, t_{i+2})``, each a
  mean over the positions that have a target; weight 0.3 and the bias's
  step 0.001 are DeepSeek-V3's (section 4.2), the config has neither.
* Attention may be computed a group of heads at a time, each group a
  ``jax.checkpoint`` (``head_groups``), and a layer may be one too
  (``remat``): the same numbers, less memory for ``jax.grad`` at 4,096
  tokens beside the engine's state.
"""
from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _f(x):
    return jnp.asarray(x).astype(F32)


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * _f(scale)


def rope(x, pos, theta):
    """``x`` [S, ..., rd]: neighbouring pairs turned by ``pos · freq``."""
    rd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, rd, 2, dtype=F32) / rd))
    ang = pos.astype(F32)[:, None] * inv[None, :]                # [S, rd/2]
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (rd // 2,)
    c, s = jnp.cos(ang).reshape(shape), jnp.sin(ang).reshape(shape)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * c - b * s, a * s + b * c], axis=-1).reshape(x.shape)


class Reference:
    """``sizes``: the published keys (``hidden_size``, ``num_attention_heads``,
    ``q_lora_rank``, ``kv_lora_rank``, ``qk_nope_head_dim``,
    ``qk_rope_head_dim``, ``v_head_dim``, ``num_experts_per_tok``,
    ``routed_scaling_factor``, ``norm_topk_prob``, ``rms_norm_eps``,
    ``rope_theta``) and the share's: ``router_outputs`` (E_all),
    ``expert_offset``, ``mtp_loss_weight``, ``bias_update_rate``."""

    def __init__(self, sizes: Dict, head_groups: int = 1, remat: bool = False):
        self.z = dict(sizes)
        self.head_groups = head_groups
        self.remat = remat

    # ------------------------------------------------------------- layers
    def attention(self, h, w: Dict, pos):
        z = self.z
        H, dn, rd, dv = (z["num_attention_heads"], z["qk_nope_head_dim"],
                         z["qk_rope_head_dim"], z["v_head_dim"])
        R, eps = z["kv_lora_rank"], z["rms_norm_eps"]
        S = h.shape[0]
        c_q = rms_norm(h @ _f(w["q_a"]), w["q_a_norm"], eps)
        q = (c_q @ _f(w["q_b"])).reshape(S, H, dn + rd)
        q = jnp.concatenate([q[..., :dn], rope(q[..., dn:], pos,
                                               z["rope_theta"])], axis=-1)
        ckv = h @ _f(w["kv_a"])
        c_kv = rms_norm(ckv[:, :R], w["kv_a_norm"], eps)
        k_r = rope(ckv[:, R:], pos, z["rope_theta"])             # [S, rd]
        kv = (c_kv @ _f(w["kv_b"])).reshape(S, H, dn + dv)
        k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(
            k_r[:, None, :], (S, H, rd))], axis=-1)
        v = kv[..., dn:]
        causal = pos[:, None] >= pos[None, :]

        def heads(q, k, v):
            s = jnp.einsum("shd,thd->hst", q, k) / jnp.sqrt(F32(dn + rd))
            p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
            return jnp.einsum("hst,thd->shd", p, v)

        G = self.head_groups
        if G == 1:
            o = heads(q, k, v)
        else:
            # a group of heads at a time, one body for all groups
            split = lambda x: jnp.moveaxis(  # noqa: E731
                x.reshape(S, G, H // G, x.shape[-1]), 1, 0)
            o = jax.lax.map(lambda qkv: jax.checkpoint(heads)(*qkv),
                            (split(q), split(k), split(v)))
            o = jnp.moveaxis(o, 0, 1).reshape(S, H, dv)
        return o.reshape(S, H * dv) @ _f(w["o"])

    @staticmethod
    def swiglu(h, gate, up, down):
        return (jax.nn.silu(h @ _f(gate)) * (h @ _f(up))) @ _f(down)

    def experts(self, h, w: Dict, bias):
        """→ (the experts' held part + the shared expert [S, D], pairs per
        router output [E_all], pairs of the experts held [E])."""
        z = self.z
        k, offset = z["num_experts_per_tok"], z.get("expert_offset", 0)
        s = jax.nn.sigmoid(h @ _f(w["router"]))                  # [S, E_all]
        _, idx = jax.lax.top_k(s + _f(bias), k)
        g = jnp.take_along_axis(s, idx, axis=-1)
        if z["norm_topk_prob"]:
            g = g / (jnp.sum(g, axis=-1, keepdims=True) + 1e-20)
        g = g * z["routed_scaling_factor"]
        E_all = s.shape[-1]
        chosen = jax.nn.one_hot(idx, E_all, dtype=F32)           # [S, k, E_all]
        loads = jnp.sum(chosen, axis=(0, 1))
        weight_of = jnp.einsum("sk,ske->se", g, chosen)          # [S, E_all]
        E = w["experts"]["up"].shape[0]

        def one_expert(out, xs):                                 # no sort
            gate, up, down, weight = xs
            return out + weight[:, None] * self.swiglu(h, gate, up, down), \
                None

        held = jax.lax.dynamic_slice_in_dim(weight_of, offset, E, axis=1)
        out, _ = jax.lax.scan(
            one_expert, jnp.zeros_like(h),
            (w["experts"]["gate"], w["experts"]["up"], w["experts"]["down"],
             held.T))
        sh = w["shared"]
        out = out + self.swiglu(h, sh["gate"], sh["up"], sh["down"])
        return out, loads, loads[offset:offset + E]

    def layer(self, x, w: Dict, bias, pos):
        eps = self.z["rms_norm_eps"]
        x = x + self.attention(rms_norm(x, w["attn_norm"], eps), w, pos)
        h = rms_norm(x, w["mlp_norm"], eps)
        if "router" not in w:
            zero = jnp.zeros((0,), F32)
            return x + self.swiglu(h, w["w_gate"], w["w_up"], w["w_down"]), \
                zero, zero
        out, loads, held = self.experts(h, w, bias)
        return x + out, loads, held

    # ------------------------------------------------------------ forward
    def forward(self, weights: Dict, tokens, bias) -> Dict:
        """One sequence ``tokens`` [S]; ``bias`` [expert layers, E_all], the
        main model's expert layers first, then the MTP module's.  →
        ``main_logits``, ``mtp_logits`` [S, V] (position i: of t_{i+1}, of
        t_{i+2}), ``loads`` [expert layers, E_all], ``held`` [expert layers,
        E]."""
        with jax.default_matmul_precision("highest"):
            return self._forward(weights, tokens, bias)

    def _forward(self, weights, tokens, bias):
        eps = self.z["rms_norm_eps"]
        S = tokens.shape[0]
        pos = jnp.arange(S)
        emb = _f(weights["embedding"])
        layer = jax.checkpoint(self.layer) if self.remat else self.layer
        x = emb[tokens]
        loads, held, row = [], [], 0
        for w in weights["layers"]:
            b = bias[row] if "router" in w else None
            x, l, c = layer(x, w, b, pos)
            if "router" in w:
                loads.append(l)
                held.append(c)
                row += 1
        h_main = rms_norm(x, weights["norm"], eps)
        head = _f(weights["head"])
        out = {"main_logits": h_main @ head}
        mtp = weights.get("mtp")
        if mtp is not None:
            nxt = jnp.concatenate([tokens[1:], jnp.zeros((1,), tokens.dtype)])
            both = jnp.concatenate([rms_norm(emb[nxt], mtp["enorm"], eps),
                                    rms_norm(h_main, mtp["hnorm"], eps)],
                                   axis=-1)
            y, l, c = layer(both @ _f(mtp["eh_proj"]), mtp["layer"],
                            bias[row], pos)
            loads.append(l)
            held.append(c)
            out["mtp_logits"] = rms_norm(y, mtp["norm"], eps) @ head
        out["loads"] = jnp.stack(loads)
        out["held"] = jnp.stack(held)
        return out

    # --------------------------------------------------------------- loss
    @staticmethod
    def cross_entropy(logits, targets):
        """Mean over the positions given: ``logits`` [n, V], ``targets``
        [n]."""
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, targets[:, None],
                                             axis=-1))

    def loss_terms(self, weights: Dict, tokens, bias) -> Tuple:
        """One sequence → (CE(main, t_{i+1}) over i < S - 1, CE(mtp,
        t_{i+2}) over i < S - 2, the forward's dict)."""
        out = self.forward(weights, tokens, bias)
        main = self.cross_entropy(out["main_logits"][:-1], tokens[1:])
        mtp = jnp.zeros((), F32)
        if "mtp_logits" in out:
            mtp = self.cross_entropy(out["mtp_logits"][:-2], tokens[2:])
        return main, mtp, out

    def loss(self, weights: Dict, rows: Sequence, bias):
        """The step's loss over equally long sequences: the mean of ``main +
        mtp_loss_weight · mtp`` (every sequence has as many targets)."""
        total = 0.0
        for tokens in rows:
            main, mtp, _ = self.loss_terms(weights, tokens, bias)
            total = total + main + self.z["mtp_loss_weight"] * mtp
        return total / len(rows)

    def next_bias(self, bias, loads):
        """``noaux_tc`` after a step: ``b_e += rate · sign(mean load −
        load_e)`` per expert layer, ``loads`` [expert layers, E_all] the
        step's pairs per router output over ALL its sequences."""
        loads = _f(loads)
        return _f(bias) + self.z["bias_update_rate"] * jnp.sign(
            jnp.mean(loads, axis=-1, keepdims=True) - loads)

    def grads(self, weights: Dict, rows: Sequence, bias,
              paths: List[Tuple]) -> Dict[Tuple, jnp.ndarray]:
        """``jax.grad`` of :meth:`loss` with respect to the leaves at
        ``paths`` only (a path is the keys from the top, e.g. ``("layers",
        1, "kv_a")``), one sequence at a time and added up."""
        def put(tree, path, leaf):
            if not path:
                return leaf
            if isinstance(tree, list):
                return [put(t, path[1:], leaf) if i == path[0] else t
                        for i, t in enumerate(tree)]
            return {k: put(t, path[1:], leaf) if k == path[0] else t
                    for k, t in tree.items()}

        def get(tree, path):
            for key in path:
                tree = tree[key]
            return tree

        def of_leaves(leaves, weights, tokens):
            for path, leaf in zip(paths, leaves):
                weights = put(weights, path, leaf)
            return self.loss(weights, [tokens], bias) / len(rows)

        grad = jax.jit(jax.grad(of_leaves))
        leaves = [_f(get(weights, p)) for p in paths]
        total = None
        for tokens in rows:
            g = grad(leaves, weights, tokens)
            total = g if total is None else [a + b for a, b in zip(total, g)]
        return dict(zip(paths, total))
