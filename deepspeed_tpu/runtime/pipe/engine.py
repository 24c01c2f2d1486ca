"""Pipeline-parallel training engine.

Reference analogue: ``PipelineEngine`` (runtime/pipe/engine.py:61):
``train_batch`` (:338) executes a generated instruction schedule with P2P
activation sends (:1019-1214) and per-instruction Python dispatch (:1408).

TPU-native execution: the whole fill-drain pipeline is ONE jitted
``lax.scan`` inside a ``shard_map`` over the "pipe" mesh axis.  Activations
move between stages with ``lax.ppermute`` (the ICI-neighbor p2p primitive);
XLA overlaps the permute with the next tick's compute — the overlap the
reference gets from separate CUDA streams.  Reverse-mode autodiff through the
scan replays the ring backwards, which *is* the backward pipeline; peak
memory matches 1F1B up to scheduling because each stage's saved activations
are bounded by (microbatches × per-stage layers) and remat (config
``activation_checkpoint_interval`` ≈ per-layer ``jax.checkpoint``) trades the
rest for recompute.

Composition rules mirror the reference: PP works with ZeRO stages 0-1
(engine asserts; reference PipelineEngine rejects ZeRO-2/3 the same way),
with TP (Megatron row/col sharding inside each stage, psum after o/down
projections), and DP over the "data" axis.
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ...utils.logging import log_dist
from ..engine import DeepSpeedEngine
from ..topology import (DATA, DATA_OUTER, EXPERT, PIPE, SEQ, TENSOR,
                        compat_shard_map, get_topology)


def _tp_psum(x, tp: int):
    return jax.lax.psum(x, TENSOR) if tp > 1 else x


def _tp_g_op(x, tp: int):
    """Megatron "g" operator for the hand-written 1F1B backward: forward
    all-reduce over TENSOR, backward identity.

    The 1F1B loop differentiates the PER-RANK program, so the Megatron f/g
    conjugate pair (megatron/core/tensor_parallel/mappings.py semantics)
    makes every per-rank cotangent carry the TRUE magnitude: g passes the
    (replicated) output cotangent straight to each rank's sharded branch,
    and f (below) all-reduces the partial input cotangents back to
    replicated-true.  Result: every param grad — sharded or replicated —
    is already complete on its own rank, and the 1F1B grad reduction never
    psums over TENSOR.  GPipe keeps the plain psum: shard_map autodiff
    inserts its own transposes there.
    """
    if tp == 1:
        return x

    @jax.custom_vjp
    def g_op(y):
        return jax.lax.psum(y, TENSOR)

    g_op.defvjp(lambda y: (jax.lax.psum(y, TENSOR), None),
                lambda _, ct: (ct,))
    return g_op(x)


def _tp_f_op(x, tp: int):
    """Megatron "f" operator: forward identity, backward all-reduce over
    TENSOR — placed where a replicated activation enters a tensor-sharded
    branch (see _tp_g_op)."""
    if tp == 1:
        return x

    @jax.custom_vjp
    def f_op(y):
        return y

    f_op.defvjp(lambda y: (y, None),
                lambda _, ct: (jax.lax.psum(ct, TENSOR),))
    return f_op(x)


def pipeline_lm_loss(params: Dict, batch: Any, cfg, topo, rng,
                     num_micro: int) -> jnp.ndarray:
    """GPipe fill-drain loss over the pipe axis (jit-compatible).

    Composes PP×TP×DP×SP: with seq>1, tokens are additionally sharded over
    the "seq" axis and each stage runs Ulysses all-to-all attention inside
    its layer stack (reference: SURVEY §2.2's SP strategy; the reference
    cannot compose Ulysses with its Python-dispatch pipeline — the all-to-all
    inside a ppermute tick is TPU-native headroom).
    """
    return _pipeline_lm(params, batch, cfg, topo, rng, num_micro,
                        schedule="gpipe")


def interleave_order(num_layers: int, pp: int, virtual_stages: int):
    """(order, inverse) permutations of the stacked layer axis mapping the
    canonical [L] order to the interleaved virtual-stage placement: rank s's
    contiguous PIPE shard holds global chunks {s, s+pp, ..., s+(V-1)·pp}."""
    Lc_g = num_layers // (pp * virtual_stages)
    if num_layers % (pp * virtual_stages) != 0:
        raise ValueError(f"virtual_stages={virtual_stages} × pipe={pp} "
                         f"must divide num_layers={num_layers}")
    order = np.concatenate([
        np.arange((c * pp + s) * Lc_g, (c * pp + s + 1) * Lc_g)
        for s in range(pp) for c in range(virtual_stages)])
    return order, np.argsort(order)


def pipeline_lm_loss_1f1b(params: Dict, batch: Any, cfg, topo, rng,
                          num_micro: int, loss_scale=1.0,
                          virtual_stages: int = 1,
                          layers_prepermuted: bool = False):
    """1F1B pipeline step → ``(loss, grads)`` (reference ``TrainSchedule``,
    runtime/pipe/schedule.py:189).

    Unlike the GPipe path (forward scan + autodiff replay, which keeps every
    microbatch's boundary activation alive), each lockstep tick here runs ONE
    forward slot and ONE backward slot: stage s forwards microbatch ``t-s``
    while back-propagating microbatch ``t-(2·pp-2-s)`` whose output-grad just
    arrived on the reverse ring.  In-flight state is a circular buffer of
    2·pp-1 stage INPUTS — O(pp), independent of num_micro — and the backward
    slot recomputes its stage forward from the saved input (per-stage
    activation checkpointing, the reference's default for pipe training).
    Activation ppermute (forward ring) and grad ppermute (reverse ring) both
    issue at tick end, so XLA overlaps them with the next tick's compute —
    the double-buffered p2p of the reference's separate CUDA streams.

    ``virtual_stages`` V > 1 runs the INTERLEAVED schedule (reference
    ``TrainSchedule`` with Megatron virtual-pipeline chunks): rank s holds
    layer chunks {s, s+pp, ...} of a V·pp virtual ring riding the SAME
    physical ppermute — chunk c of rank pp-1 hands to chunk c+1 of rank 0
    on the next tick with no extra hop.  Ticks shrink to 1/V of a stage, so
    the fill/drain bubble costs (pp-1)/V stage-times instead of pp-1.
    Requires num_micro % pp == 0 (microbatches flow in groups of pp).

    ``layers_prepermuted=True`` means ``params["layers"]`` already sits in
    :func:`interleave_order` layout (the PipelineEngine keeps its state that
    way): the per-step permute — a cross-pipe collective moving the whole
    weight tree twice per step — is skipped, and grads return in the SAME
    interleaved layout.
    """
    return _pipeline_lm(params, batch, cfg, topo, rng, num_micro,
                        schedule="1f1b", loss_scale=loss_scale,
                        virtual_stages=virtual_stages,
                        layers_prepermuted=layers_prepermuted)


def _pipeline_lm(params: Dict, batch: Any, cfg, topo, rng, num_micro: int,
                 schedule: str, loss_scale=1.0, virtual_stages: int = 1,
                 layers_prepermuted: bool = False):
    from ...models.transformer import apply_rope, lm_loss, rms_norm, rope_tables

    pp = topo.dims[PIPE]
    tp = topo.dims[TENSOR]
    sp = topo.dims[SEQ]
    tokens = batch["input_ids"] if isinstance(batch, dict) else batch
    if pp == 1:
        assert schedule == "gpipe", "1f1b needs pipe>1 (engine guards this)"
        return lm_loss(params, {"input_ids": tokens}, cfg, rng)
    if sp > 1 and (cfg.num_heads // tp) % sp != 0:
        raise ValueError(f"SP×PP needs local heads ({cfg.num_heads}//{tp}) "
                         f"divisible by seq={sp}")

    mesh = topo.mesh
    batch_axes = tuple(a for a in (DATA_OUTER, DATA, EXPERT) if topo.dims[a] > 1) or None

    # in_specs: params per the model's pipe/TP layout; tokens over data axes
    # (and the sequence dim over "seq" when sp>1).
    spec_tree = _pipeline_param_specs(params, cfg)
    tok_spec = P(batch_axes, SEQ if sp > 1 else None)

    def body(params, tokens):
        stage = jax.lax.axis_index(PIPE)
        B_loc, S_loc = tokens.shape            # S_loc = S/sp when sp>1
        S = S_loc * sp
        assert B_loc % num_micro == 0, "local batch must divide microbatches"
        mb = B_loc // num_micro
        tmb = tokens.reshape(num_micro, mb, S_loc)
        cos_all, sin_all = rope_tables(S, cfg.head_dim, cfg.rope_theta)
        if sp > 1:
            seq_idx = jax.lax.axis_index(SEQ)
            cos = jax.lax.dynamic_slice_in_dim(cos_all, seq_idx * S_loc, S_loc)
            sin = jax.lax.dynamic_slice_in_dim(sin_all, seq_idx * S_loc, S_loc)
        else:
            cos, sin = cos_all, sin_all
        H_loc = cfg.num_heads // tp
        KV_loc = max(cfg.num_kv_heads // tp, 1)
        dtype = params["layers"]["q_proj"]["kernel"].dtype

        def attend(q, k, v):
            from ...models.transformer import _xla_attention
            from ...sequence.layer import _seq_all_to_all

            if sp == 1:
                return _xla_attention(q, k, v, causal=True)
            # Ulysses inside the pipeline tick: scatter heads / gather seq
            q = _seq_all_to_all(q, scatter_heads=True)
            k = _seq_all_to_all(k, scatter_heads=True)
            v = _seq_all_to_all(v, scatter_heads=True)
            o = _xla_attention(q, k, v, causal=True)
            return _seq_all_to_all(o, scatter_heads=False)

        if schedule == "1f1b":
            tp_reduce, tp_enter = _tp_g_op, _tp_f_op
        else:
            tp_reduce, tp_enter = _tp_psum, lambda x, _: x

        def one_layer(x, lp):
            h = rms_norm(x, lp["attn_norm"]["scale"], cfg.norm_eps)
            h = tp_enter(h, tp)
            q = (h @ lp["q_proj"]["kernel"]).reshape(mb, S_loc, H_loc, cfg.head_dim)
            k = (h @ lp["k_proj"]["kernel"]).reshape(mb, S_loc, KV_loc, cfg.head_dim)
            v = (h @ lp["v_proj"]["kernel"]).reshape(mb, S_loc, KV_loc, cfg.head_dim)
            q, k = apply_rope(q, cos, sin), apply_rope(k, cos, sin)
            if sp > 1 and KV_loc != H_loc and KV_loc % sp != 0:
                # GQA kv heads don't split over the seq ranks: expand before
                # the all-to-all (pays H/KV× payload — only when unavoidable;
                # when KV_loc % sp == 0 the kv heads ride the wire as-is and
                # _xla_attention repeats them after the gather)
                k = jnp.repeat(k, H_loc // KV_loc, axis=2)
                v = jnp.repeat(v, H_loc // KV_loc, axis=2)
            o = attend(q, k, v)
            x = x + tp_reduce(o.reshape(mb, S_loc, -1) @ lp["o_proj"]["kernel"], tp)
            h = rms_norm(x, lp["mlp_norm"]["scale"], cfg.norm_eps)
            h = tp_enter(h, tp)
            gate = jax.nn.silu(h @ lp["gate_proj"]["kernel"])
            up = h @ lp["up_proj"]["kernel"]
            x = x + tp_reduce((gate * up) @ lp["down_proj"]["kernel"], tp)
            return x, None

        layer_fn = jax.checkpoint(one_layer) if cfg.remat else one_layer

        def stage_fn(p, x):
            x, _ = jax.lax.scan(layer_fn, x, p["layers"])
            return x

        # Labels for every microbatch, computed BEFORE the pipeline loop:
        # the SP label shift is a SEQ collective and must run uniformly on
        # all devices — it cannot live inside the stage-gated emit cond.
        if sp > 1:
            # left-shift across seq shards: shard i's last label is shard
            # i+1's first token (last shard pads with ignore)
            shift = [(i, (i - 1) % sp) for i in range(sp)]
            nxt_first = jax.lax.ppermute(tmb[:, :, :1], SEQ, shift)
            seq_i = jax.lax.axis_index(SEQ)
            tail = jnp.where(seq_i == sp - 1, -100, nxt_first)
            label_mb = jnp.concatenate([tmb[:, :, 1:], tail], axis=2)
        else:
            label_mb = jnp.pad(tmb[:, :, 1:], ((0, 0), (0, 0), (0, 1)),
                               constant_values=-100)

        def loss_of(p, h, labels):
            """Per-shard (sum, count) over this rank's label slice."""
            h = rms_norm(h, p["norm_f"]["scale"], cfg.norm_eps)
            if cfg.tie_embeddings:
                logits = h @ p["embed"]["embedding"].T
            else:
                logits = h @ p["lm_head"]["kernel"]
            logits = logits.astype(jnp.float32)
            logp = jax.nn.log_softmax(logits, axis=-1)
            valid = labels >= 0
            safe = jnp.where(valid, labels, 0)
            tok_lp = jnp.take_along_axis(logp, safe[..., None], axis=-1)[..., 0]
            return -jnp.sum(tok_lp * valid), jnp.sum(valid).astype(jnp.float32)

        D = cfg.hidden_size
        perm = [(i, (i + 1) % pp) for i in range(pp)]
        sum_axes = (PIPE,) + ((SEQ,) if sp > 1 else ()) + (batch_axes or ())

        def f_tick(p, toks_in, buf, labels, emit):
            """One stage slot: embed-or-receive, stage layers, (masked) loss.
            Parameters are explicit args so the 1F1B backward slot can
            jax.vjp through it."""
            x_embed = jnp.take(p["embed"]["embedding"], toks_in, axis=0
                               ).astype(dtype)
            x = jnp.where(stage == 0, x_embed, buf)
            h = stage_fn(p, x)
            sl, cn = jax.lax.cond(
                emit, lambda: loss_of(p, h, labels),
                lambda: (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)))
            return h, sl, cn

        if schedule == "gpipe":
            T = num_micro + pp - 1

            def tick(carry, t):
                buf, loss_acc, count_acc = carry
                in_idx = jnp.clip(t, 0, num_micro - 1)
                toks_in = jax.lax.dynamic_index_in_dim(tmb, in_idx, 0,
                                                       keepdims=False)
                out_idx = jnp.clip(t - (pp - 1), 0, num_micro - 1)
                labels_out = jax.lax.dynamic_index_in_dim(label_mb, out_idx, 0,
                                                          keepdims=False)
                is_emit = jnp.logical_and(stage == pp - 1, t >= pp - 1)
                h, mb_loss, mb_count = f_tick(params, toks_in, buf,
                                              labels_out, is_emit)
                buf_next = jax.lax.ppermute(h, PIPE, perm)
                return (buf_next, loss_acc + mb_loss, count_acc + mb_count), None

            buf0 = jnp.zeros((mb, S_loc, D), dtype)
            (_, loss_acc, count_acc), _ = jax.lax.scan(
                tick, (buf0, jnp.zeros((), jnp.float32),
                       jnp.zeros((), jnp.float32)), jnp.arange(T))
            # Token-weighted mean over pipe stages (only the last stage
            # emitted), seq shards, and data ranks; the returned scalar must
            # be identical on every shard (out_spec is replicated).
            loss = jax.lax.psum(loss_acc, sum_axes) / \
                jnp.maximum(jax.lax.psum(count_acc, sum_axes), 1.0)
            return loss

        # ---------------- 1F1B schedule (V virtual stages/rank) ------- #
        # Virtual stage vs = c·pp + s rides the physical ring: chunk c of
        # rank pp-1 hands to chunk c+1 of rank 0 next tick.  Microbatch
        # m = G·pp + j has offset off(m) = G·V·pp + j; it forwards through
        # vs at tick off+vs and backwards at tick off + 2(V·pp-1) - vs.
        # V = 1 reduces to plain 1F1B (off(m) = m).  The last virtual
        # stage's B slot is the same tick as its F slot (immediate loss
        # backward — the 1F1B signature).  The input ring holds 2·V·pp - 1
        # slots: a saved input lives 2(V·pp-1-vs) ticks.
        V = virtual_stages
        if V > 1 and num_micro % pp != 0:
            raise ValueError(f"interleaved 1F1B (virtual_stages={V}) needs "
                             f"num_micro ({num_micro}) % pp ({pp}) == 0")
        L_loc = params["layers"]["q_proj"]["kernel"].shape[0]
        if L_loc % V != 0:
            raise ValueError(f"virtual_stages={V} must divide the per-rank "
                             f"layer count {L_loc}")
        vpp = V * pp
        rev_perm = [(i, (i - 1) % pp) for i in range(pp)]
        R = 2 * vpp - 1
        off_max = num_micro - 1 if V == 1 else \
            (num_micro // pp - 1) * vpp + pp - 1
        T = off_max + 2 * (vpp - 1) + 1
        f32z = jnp.zeros((), jnp.float32)

        def slot_f(t):
            """F slot of this rank at tick t → (m, chunk, valid)."""
            q = t - stage
            if V == 1:
                return q, jnp.zeros((), q.dtype), \
                    jnp.logical_and(q >= 0, q < num_micro)
            c = jnp.mod(q // pp, V)
            m = (q // vpp) * pp + jnp.mod(q, pp)
            return m, c, jnp.logical_and(q >= 0, m < num_micro)

        def slot_b(t):
            """B slot: the unique chunk c whose off = t - 2(vpp-1) + c·pp +
            stage lands on a group boundary residue (< pp)."""
            if V == 1:
                m = t - (2 * pp - 2 - stage)
                return m, jnp.zeros((), m.dtype), \
                    jnp.logical_and(m >= 0, m < num_micro)
            m_sel = jnp.zeros((), t.dtype)
            c_sel = jnp.zeros((), t.dtype)
            ok = jnp.zeros((), jnp.bool_)
            for c in range(V):
                off = t - 2 * (vpp - 1) + c * pp + stage
                j = jnp.mod(off, vpp)
                m = (off // vpp) * pp + j
                valid = (off >= 0) & (j < pp) & (m < num_micro)
                m_sel = jnp.where(valid, m, m_sel)
                c_sel = jnp.where(valid, c, c_sel)
                ok = jnp.logical_or(ok, valid)
            return m_sel, c_sel, ok

        Lc = L_loc // V

        def f_tick_v(p, toks_in, buf, labels, chunk):
            """One VIRTUAL stage slot: embed at vs 0, chunk layers, loss at
            vs V·pp-1.  Differentiable in (p, buf)."""
            is_first_vs = jnp.logical_and(stage == 0, chunk == 0)
            is_last_vs = jnp.logical_and(stage == pp - 1, chunk == V - 1)
            x_embed = jnp.take(p["embed"]["embedding"], toks_in, axis=0
                               ).astype(dtype)
            x = jnp.where(is_first_vs, x_embed, buf)
            chunk_layers = jax.tree.map(
                lambda a: jax.lax.dynamic_slice_in_dim(a, chunk * Lc, Lc, 0),
                p["layers"])
            h = stage_fn({**p, "layers": chunk_layers}, x)
            sl, cn = jax.lax.cond(
                is_last_vs, lambda: loss_of(p, h, labels),
                lambda: (f32z, f32z))
            return h, sl, cn

        def tick(carry, t):
            ring, abuf, gbuf, grad_acc, loss_acc, count_acc = carry
            ring, abuf, loss_acc, count_acc = _f_half(
                ring, abuf, loss_acc, count_acc, t)
            gbuf, grad_acc = _b_half(ring, gbuf, grad_acc, t)
            return (ring, abuf, gbuf, grad_acc, loss_acc, count_acc), None

        def _f_half(ring, abuf, loss_acc, count_acc, t):
            """Forward slot: save input to the ring, run the chunk, emit
            loss at the last virtual stage, permute the activation."""
            m_f, c_f, f_valid = slot_f(t)
            idx_f = jnp.clip(m_f, 0, num_micro - 1)
            toks_f = jax.lax.dynamic_index_in_dim(tmb, idx_f, 0, keepdims=False)
            labels_f = jax.lax.dynamic_index_in_dim(label_mb, idx_f, 0,
                                                    keepdims=False)
            ring = jax.lax.dynamic_update_index_in_dim(
                ring, abuf, jnp.mod(t, R), 0)
            h, sl, cn = f_tick_v(params, toks_f, abuf, labels_f, c_f)
            emit = jnp.logical_and(
                jnp.logical_and(stage == pp - 1, c_f == V - 1), f_valid)
            loss_acc = loss_acc + jnp.where(emit, sl, 0.0)
            count_acc = count_acc + jnp.where(emit, cn, 0.0)
            abuf_next = jax.lax.ppermute(h, PIPE, perm)
            return ring, abuf_next, loss_acc, count_acc

        def _b_half(ring, gbuf, grad_acc, t):
            """Backward slot: vjp of the saved-input chunk, accumulate
            grads, permute the cotangent down the reverse ring."""
            m_b, c_b, b_valid = slot_b(t)
            idx_b = jnp.clip(m_b, 0, num_micro - 1)
            toks_b = jax.lax.dynamic_index_in_dim(tmb, idx_b, 0, keepdims=False)
            labels_b = jax.lax.dynamic_index_in_dim(label_mb, idx_b, 0,
                                                    keepdims=False)
            vs_b = c_b * pp + stage
            x_saved = jax.lax.dynamic_index_in_dim(
                ring, jnp.mod(t - 2 * (vpp - 1) + 2 * vs_b, R), 0,
                keepdims=False)
            _, vjp_fn = jax.vjp(
                lambda p, bf: f_tick_v(p, toks_b, bf, labels_b, c_b)[:2],
                params, x_saved)
            # Zero cotangents on invalid slots make dp/dbuf exactly zero
            # (vjp is linear) — the fill/drain garbage never touches grads.
            b_is_last = jnp.logical_and(stage == pp - 1, c_b == V - 1)
            g_h = jnp.where(jnp.logical_and(b_valid, ~b_is_last), 1.0, 0.0) \
                * gbuf
            g_sl = jnp.where(jnp.logical_and(b_valid, b_is_last),
                             jnp.asarray(loss_scale, jnp.float32), 0.0)
            dp, dbuf = vjp_fn((g_h.astype(dtype), g_sl))
            grad_acc = jax.tree.map(
                lambda a, g: a + g.astype(jnp.float32), grad_acc, dp)
            gbuf_next = jax.lax.ppermute(dbuf.astype(dtype), PIPE, rev_perm)
            return gbuf_next, grad_acc

        # Phase-split schedule (round-5 bubble fix): for the first vpp-1
        # ticks NO rank has a valid backward slot (the earliest B is the
        # immediate loss-backward of microbatch 0's last virtual stage at
        # t = vpp-1), and for the last vpp-1 ticks no rank has a valid
        # forward slot (the last F is at off_max + vpp - 1).  A single
        # uniform scan pays the full F+B body on those ticks anyway —
        # masked-out compute, but real time — which is exactly why the
        # measured bubble was (vpp+pp-2)/... and did NOT shrink with V.
        # Splitting into warmup (F-only body), steady (F+B), and drain
        # (B-only) scans keeps the slot formulas and dataflow identical
        # while the fill/drain ticks cost only half a tick, restoring the
        # textbook bubble: (pp-1) full-tick equivalents out of
        # M*V + pp - 1 — i.e. the (pp-1)/V interleaving win.
        def warm_tick(carry, t):
            ring, abuf, gbuf, grad_acc, loss_acc, count_acc = carry
            ring, abuf, loss_acc, count_acc = _f_half(
                ring, abuf, loss_acc, count_acc, t)
            return (ring, abuf, gbuf, grad_acc, loss_acc, count_acc), None

        def drain_tick(carry, t):
            ring, abuf, gbuf, grad_acc, loss_acc, count_acc = carry
            gbuf, grad_acc = _b_half(ring, gbuf, grad_acc, t)
            return (ring, abuf, gbuf, grad_acc, loss_acc, count_acc), None

        W = vpp - 1                        # fill ticks: F-only
        steady_end = off_max + vpp         # last F tick is steady_end - 1
        ring0 = jnp.zeros((R, mb, S_loc, D), dtype)
        buf0 = jnp.zeros((mb, S_loc, D), dtype)
        grad0 = jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32), params)
        carry = (ring0, buf0, buf0, grad0, f32z, f32z)
        carry, _ = jax.lax.scan(warm_tick, carry, jnp.arange(W))
        carry, _ = jax.lax.scan(tick, carry, jnp.arange(W, steady_end))
        carry, _ = jax.lax.scan(drain_tick, carry, jnp.arange(steady_end, T))
        (_, _, _, grads, loss_acc, count_acc) = carry

        total_count = jnp.maximum(jax.lax.psum(count_acc, sum_axes), 1.0)
        loss = jax.lax.psum(loss_acc, sum_axes) / total_count
        # Grad normalization matches the loss: each microbatch's loss_of
        # returns a SUM over tokens, so divide by the global token count.
        # Cross-shard reduction rule: a leaf's grad is partial on every mesh
        # axis its partition spec does NOT mention (data/seq always; pipe for
        # the replicated embed/norm/head leaves) — with the exception of
        # TENSOR: the Megatron f/g operators inside the layer already leave
        # every per-rank grad complete w.r.t. the tensor axis (see _tp_g_op).
        def reduce_leaf(g, spec):
            mentioned = set()
            for entry in spec:
                if entry is None:
                    continue
                mentioned.update(entry if isinstance(entry, (tuple, list))
                                 else (entry,))
            axes = tuple(a for a in (PIPE, DATA_OUTER, DATA, EXPERT, SEQ)
                         if topo.dims[a] > 1 and a not in mentioned)
            g = g / total_count
            return jax.lax.psum(g, axes) if axes else g

        grads = jax.tree.map(reduce_leaf, grads, spec_tree,
                             is_leaf=lambda x: isinstance(x, P))
        return loss, grads

    if schedule == "gpipe":
        return compat_shard_map(body, mesh=mesh,
                                in_specs=(spec_tree, tok_spec),
                                out_specs=P())(params, tokens)

    if virtual_stages > 1 and not layers_prepermuted:
        # Interleaved layer placement: virtual stage vs = c·pp + s means
        # rank s owns global layer chunks {s, s+pp, ..., s+(V-1)·pp}, local
        # chunk order c = 0..V-1 — but the contiguous PIPE shard gives rank
        # s rows [s·L/pp, ...).  Permute the stacked layer axis so the
        # contiguous shard IS the interleaved assignment (and un-permute the
        # returned grads).  The PipelineEngine keeps its state prepermuted
        # so the train step never pays this cross-pipe collective; this
        # branch serves direct/functional callers.
        order, inv = interleave_order(cfg.num_layers, pp, virtual_stages)
        params = {**params, "layers": jax.tree.map(
            lambda a: jnp.take(a, order, axis=0), params["layers"])}
    elif virtual_stages > 1:
        interleave_order(cfg.num_layers, pp, virtual_stages)  # validates

    loss, grads = compat_shard_map(
        body, mesh=mesh, in_specs=(spec_tree, tok_spec),
        out_specs=(P(), spec_tree))(params, tokens)
    if virtual_stages > 1 and not layers_prepermuted:
        grads = {**grads, "layers": jax.tree.map(
            lambda a: jnp.take(a, inv, axis=0), grads["layers"])}
    return loss, grads


def pipeline_module_loss(module, params: Dict, batch: Any, rng,
                         num_micro: int, topo) -> jnp.ndarray:
    """GPipe loss for an arbitrary (heterogeneous) ``PipelineModule``
    LayerSpec list (reference: PipelineEngine executing any LayerSpec model,
    runtime/pipe/engine.py:709 _exec_forward_pass).

    SPMD strategy: every device traces ALL stage programs and selects its
    own via ``lax.switch`` on the pipe-axis index — heterogeneous stages
    can't ride one stacked-scan array, so stage params are replicated over
    the pipe axis (generality path; the homogeneous transformer fast path
    keeps pipe-sharded params).  Constraint: inter-stage activations must
    share one shape/dtype (the ppermute boundary); the final stage's output
    feeds ``module.loss_fn(h, labels)``.
    """
    pp = topo.dims[PIPE]
    if module.loss_fn is None:
        raise ValueError("PipelineModule needs loss_fn=(h, labels) -> scalar")
    x = batch["x"] if isinstance(batch, dict) else batch
    labels = batch.get("labels") if isinstance(batch, dict) else None
    if pp == 1:
        out = module.apply_sequential(params, x, rng=rng)
        return module.loss_fn(out, labels)

    mesh = topo.mesh
    batch_axes = tuple(a for a in (DATA_OUTER, DATA, EXPERT)
                       if topo.dims[a] > 1) or None
    parts = module.parts

    def stage_apply(s, p, h, r):
        return module.apply_range(p, parts[s], parts[s + 1], h, rng=r)

    def body(params, x, labels):
        stage = jax.lax.axis_index(PIPE)
        B_loc = x.shape[0]
        assert B_loc % num_micro == 0
        mb = B_loc // num_micro
        xmb = x.reshape((num_micro, mb) + x.shape[1:])
        lmb = labels.reshape((num_micro, mb) + labels.shape[1:]) \
            if labels is not None else None

        # boundary activation shape = stage 0's output (must be uniform)
        bound = jax.eval_shape(lambda h: stage_apply(0, params, h, rng),
                               jax.ShapeDtypeStruct((mb,) + x.shape[1:], x.dtype))

        fns = [(lambda s: lambda buf, x_in: stage_apply(
            s, params, x_in if s == 0 else buf, rng))(s) for s in range(pp)]

        perm = [(i, (i + 1) % pp) for i in range(pp)]
        T = num_micro + pp - 1

        def tick(carry, t):
            buf, loss_acc = carry
            in_idx = jnp.clip(t, 0, num_micro - 1)
            x_in = jax.lax.dynamic_index_in_dim(xmb, in_idx, 0, keepdims=False)
            h = jax.lax.switch(stage, fns, buf, x_in)
            out_idx = jnp.clip(t - (pp - 1), 0, num_micro - 1)
            l_out = jax.lax.dynamic_index_in_dim(lmb, out_idx, 0, keepdims=False) \
                if lmb is not None else None
            is_emit = jnp.logical_and(stage == pp - 1, t >= pp - 1)
            # loss_fn runs UNCONDITIONALLY on every stage and is masked after:
            # user code may contain collectives, which must execute uniformly
            # (a stage-gated cond would hang them — same hazard the lm path's
            # label ppermute avoids by hoisting).
            mb_loss = jnp.where(is_emit,
                                module.loss_fn(h, l_out).astype(jnp.float32),
                                0.0)
            return (jax.lax.ppermute(h, PIPE, perm), loss_acc + mb_loss), None

        buf0 = jnp.zeros(bound.shape, bound.dtype)
        (_, loss_acc), _ = jax.lax.scan(
            tick, (buf0, jnp.zeros((), jnp.float32)), jnp.arange(T))
        loss = jax.lax.psum(loss_acc, PIPE) / num_micro
        if batch_axes:
            dp = 1
            for a in batch_axes:
                dp *= topo.dims[a]
            loss = jax.lax.psum(loss, batch_axes) / dp
        return loss

    spec_tree = jax.tree.map(lambda _: P(), params)
    data_spec = P(batch_axes)
    if labels is None:
        fn = lambda p, xx: body(p, xx, None)
        in_specs, args = (spec_tree, data_spec), (params, x)
    else:
        fn, in_specs, args = body, (spec_tree, data_spec, data_spec), \
            (params, x, labels)
    return compat_shard_map(fn, mesh=mesh, in_specs=in_specs,
                            out_specs=P())(*args)


def _pipeline_param_specs(params, cfg):
    """Specs used as shard_map in_specs: layers pipe(+TP)-sharded, tied
    embed/norm/head replicated."""
    from ...models.transformer import partition_specs

    base = partition_specs(cfg)
    base["embed"] = {"embedding": P(None, None)}
    if "lm_head" in base:
        base["lm_head"] = {"kernel": P(None, None)}

    def pipeify(spec):
        entries = list(spec)
        entries[0] = PIPE
        return P(*entries)

    base["layers"] = jax.tree.map(pipeify, base["layers"],
                                  is_leaf=lambda s: isinstance(s, P))
    # prune to params actually present (tied embeddings drop lm_head)
    return {k: base[k] for k in params}


class PipelineEngine(DeepSpeedEngine):
    """Engine for PipelinedCausalLM / PipelineModule models."""

    def __init__(self, model, config, topology=None, **kwargs):
        topology = topology or get_topology()
        if config.zero_config.stage > 1:
            raise ValueError(
                "PipelineEngine supports ZeRO stages 0-1 only (reference "
                "PipelineEngine has the same restriction)")
        self.num_micro = config.gradient_accumulation_steps
        self._pipe_model = model
        super().__init__(model=model, config=config, topology=topology, **kwargs)
        self.is_pipe_parallel = topology.get_pipe_parallel_world_size() > 1
        # Interleaved virtual stages: keep state.params["layers"] PERMANENTLY
        # in interleave_order layout so the hot step never pays the
        # cross-pipe permute collective (twice per step for weights+grads);
        # checkpoints and the eval path convert back to canonical [L] order.
        self._vs_order = self._vs_inv = None
        V = config.pipeline.virtual_stages
        if self._use_1f1b() and V > 1:
            pp = topology.get_pipe_parallel_world_size()
            self._vs_order, self._vs_inv = interleave_order(
                model.config.num_layers, pp, V)
            self.state = self.state.replace(
                params=self._permute_layers(self.state.params, self._vs_order))
        log_dist(f"pipeline engine: stages={topology.get_pipe_parallel_world_size()} "
                 f"micro_batches={self.num_micro}", ranks=[0])

    # ---------------- interleaved-layout plumbing --------------------- #
    def _permute_layers(self, params, order):
        """Permute the stacked layer axis of a params-shaped tree, keeping
        each leaf's sharding (one collective at init/ckpt time — not per
        step)."""
        shardings = self.param_shardings["layers"]
        idx = jnp.asarray(order)
        layers = jax.tree.map(
            lambda a, s: jax.device_put(jnp.take(a, idx, axis=0), s),
            params["layers"], shardings)
        return {**params, "layers": layers}

    def _convert_state_layout(self, state, order):
        """Apply the layer permutation to every params-shaped component of
        an EngineState (params + optimizer moments + grad accumulator)."""
        param_struct = jax.tree_util.tree_structure(state.params)
        param_leaves = jax.tree.leaves(state.params)

        def mirrors(node):
            if jax.tree_util.tree_structure(node) != param_struct:
                return False
            return all(getattr(l, "shape", None) == p.shape
                       for l, p in zip(jax.tree.leaves(node), param_leaves))

        def fix(node):
            return self._permute_layers(node, order) if mirrors(node) else node

        new_opt = jax.tree.map(fix, state.opt_state, is_leaf=mirrors)
        new_acc = self._permute_layers(state.grad_acc, order) \
            if state.grad_acc is not None and mirrors(state.grad_acc) \
            else state.grad_acc
        return state.replace(params=self._permute_layers(state.params, order),
                             opt_state=new_opt, grad_acc=new_acc)

    def save_checkpoint(self, save_dir, tag=None, **kw):
        """Checkpoints always hold the CANONICAL [L] layer order so they
        reload under any (pp, virtual_stages, schedule) config."""
        if self._vs_inv is None:
            return super().save_checkpoint(save_dir, tag=tag, **kw)
        live = self.state
        self.state = self._convert_state_layout(live, self._vs_inv)
        try:
            return super().save_checkpoint(save_dir, tag=tag, **kw)
        finally:
            self.state = live

    def load_checkpoint(self, load_dir, tag=None, **kw):
        out = super().load_checkpoint(load_dir, tag=tag, **kw)
        if self._vs_order is not None and out[0] is not None:
            # re-interleave ONLY what the base load actually replaced with
            # canonical-order data: a missing checkpoint leaves the live
            # (already interleaved) state untouched, and a params-only load
            # must not re-permute the untouched optimizer moments
            params_only = kw.get("load_module_only") or \
                not kw.get("load_optimizer_states", True)
            if params_only:
                self.state = self.state.replace(
                    params=self._permute_layers(self.state.params,
                                                self._vs_order))
            else:
                self.state = self._convert_state_layout(self.state,
                                                        self._vs_order)
        return out

    def _resolve_loss_fn(self, model):
        from .module import PipelineModule

        if isinstance(model, PipelineModule):
            # arbitrary LayerSpec lists with a user loss (no hard-wired
            # CausalLM recipe)
            def fn(params, batch, rng):
                return pipeline_module_loss(
                    model, params, batch, rng, self.num_micro,
                    self.topology or get_topology())

            return fn
        cfg = model.config

        def fn(params, batch, rng):
            inv = getattr(self, "_vs_inv", None)
            if inv is not None:
                # eval path: engine state lives in interleaved layout; the
                # GPipe forward expects canonical order (cold path — the
                # permute collective is acceptable here)
                params = self._permute_layers(params, inv)
            return pipeline_lm_loss(params, batch, cfg, self.topology or get_topology(),
                                    rng, self.num_micro)

        return fn

    def _use_1f1b(self) -> bool:
        from .module import PipelineModule

        topo = self.topology or get_topology()
        return (self.config.pipeline.schedule == "1f1b"
                and topo.get_pipe_parallel_world_size() > 1
                and not isinstance(self._pipe_model, PipelineModule))

    # The pipeline loop consumes all microbatches in one jitted call, so the
    # outer engine runs with gas=1 semantics.
    def _build_train_batch_fn(self):
        use_1f1b = self._use_1f1b()
        topo = self.topology or get_topology()

        def step_fn(state, batch):
            rng, sub = jax.random.split(state.rng)
            if use_1f1b:
                # the 1F1B loop produces grads itself (fwd/bwd interleaved
                # per tick) — no autodiff over the pipeline scan
                p = jax.tree.map(lambda x: x.astype(self.compute_dtype),
                                 state.params)
                loss, grads = pipeline_lm_loss_1f1b(
                    p, batch, self._pipe_model.config, topo, sub,
                    self.num_micro, loss_scale=state.scaler.scale,
                    virtual_stages=self.config.pipeline.virtual_stages,
                    layers_prepermuted=self._vs_order is not None)
                grads = self._constrain_grads(grads)
            else:
                loss, grads = self._loss_and_grads(state.params, batch, sub,
                                                   state.scaler)
            new_state = self._apply_update(state, grads)
            return new_state.replace(
                micro_step=state.micro_step + self.num_micro, rng=rng), loss

        return jax.jit(step_fn, donate_argnums=(0,))

    def train_batch(self, batch=None, data_iter=None):
        if batch is None and data_iter is not None:
            batch = next(data_iter)
        # No outer gas reshape: the jitted pipeline consumes the whole batch.
        if "train_batch" not in self._compiled:
            self._compiled["train_batch"] = self._build_train_batch_fn()
        self.tput_timer.start()
        self.state, loss = self._compiled["train_batch"](self.state, batch)
        self.tput_timer.stop(sync=loss)
        self._write_monitor_events(loss)
        return loss
