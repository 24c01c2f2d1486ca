"""The plain reference's routing figures: the load-balancing loss is taken
over all tokens of the batch, and the expert loads count every (token,
choice) pair once."""
import numpy as np
import pytest

import jax.numpy as jnp

from reference import decoder

SIZES = dict(num_attention_heads=2, num_key_value_heads=1, rope_theta=1e4,
             rms_norm_eps=1e-5, num_experts_per_tok=2)
D, F, E, V = 16, 24, 4, 50


def weights(rng):
    def w(*shape):
        return jnp.asarray(rng.normal(0, 0.3, size=shape), jnp.float32)

    layer = {"attn_norm": jnp.ones(D), "wq": w(D, D), "wk": w(D, D // 2),
             "wv": w(D, D // 2), "wo": w(D, D), "mlp_norm": jnp.ones(D),
             "router": w(D, E), "w_gate": w(E, D, F), "w_up": w(E, D, F),
             "w_down": w(E, F, D)}
    return {"embedding": w(V, D), "norm": jnp.ones(D), "head": w(D, V),
            "layers": [lambda: layer]}


def test_load_balance_of_even_routing_is_one():
    stats = jnp.stack([jnp.full((E,), 10.0), jnp.full((E,), 5.0)])
    assert decoder.load_balance(stats, tokens=20) == pytest.approx(1.0)


def test_balance_is_over_the_whole_batch_and_loads_count_every_pair():
    rng = np.random.default_rng(0)
    ws = weights(rng)
    rows = [jnp.asarray(rng.integers(0, V, size=12), jnp.int32)
            for _ in range(3)]
    ref = decoder.Reference(SIZES)
    losses, kept, routing = ref.run(rows, ws, keep_logits=1)
    assert len(losses) == 3 and kept[0].shape == (12, V)
    (loads,) = routing["expert_loads"]
    assert sum(loads) == 3 * 12 * 2
    # the same figure from the three rows run as one batch of one each
    parts = [ref.run([row], ws)[2] for row in rows]
    assert [sum(p["expert_loads"][0][e] for p in parts)
            for e in range(E)] == loads
    per_row = np.mean([p["balance"] for p in parts])
    assert routing["balance"] != pytest.approx(per_row, abs=1e-6)
    assert routing["balance"] >= 1.0 - 1e-6
