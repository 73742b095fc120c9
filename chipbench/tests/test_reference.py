"""The reference rebuilds the program's weights from the seed (bit for bit
eagerly, to an ulp compiled whole), and computes the same loss and
gradient as the program's model at float32."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tiny  # noqa: F401  (puts the benchmark and the program on the path)
import bench
import reference


@pytest.mark.parametrize("workload", tiny.CELLS)
def test_weights_match_the_program(workload):
    from repro.models import api

    c = tiny.cell(workload)
    seed = bench.derive_seeds(2**31 + 77)[0]
    cfg = c.model.model_config(c.config)
    want = api.init(jax.random.PRNGKey(seed), cfg)
    k = c.model.dims(c.config)
    eager = c.model.init_params(seed, k)
    assert jax.tree.structure(eager) == jax.tree.structure(want)
    for g, w in zip(jax.tree.leaves(eager), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    # compiled whole, XLA fuses the scaling into the sampler: an ulp apart
    jitted = jax.jit(c.model.init_params, static_argnums=1)(seed, k)
    for g, w in zip(jax.tree.leaves(jitted), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=3e-7,
                                   atol=0)


@pytest.mark.parametrize("workload", tiny.CELLS)
def test_loss_and_gradient_match_the_program_at_f32(workload):
    from repro.models import api

    c = tiny.cell(workload, seq_len=32)
    cfg = dataclasses.replace(c.model.model_config(c.config),
                              compute_dtype="float32")
    k = c.model.dims(c.config)
    params = c.model.init_params(5, k)
    batch = bench.Feed(k.vocab, c.traffic, 9).next()
    with jax.default_matmul_precision("highest"):
        want_l, want_g = jax.value_and_grad(api.make_loss(cfg))(
            params, jax.tree.map(jnp.asarray, batch))
        got_l, got_g = reference.batch_grad(c.model, params, batch["tokens"],
                                            batch["labels"], k)
    assert abs(float(got_l) - float(want_l)) < 1e-5 * abs(float(want_l))
    for g, w in zip(jax.tree.leaves(got_g), jax.tree.leaves(want_g)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=2e-4, atol=1e-7)


def test_seeds_past_32_bits_give_the_same_work():
    a, b = bench.derive_seeds(2**33 + 5), bench.derive_seeds(2**33 + 5)
    assert a == b and a[0] < 2**31
    assert bench.derive_seeds(2**33 + 6) != a
