"""`ops/slot_state.py`: the pass over a slot's state, interpreted, with a
toy body at the three state kernels' shapes; and the one platform question
of `ops/`. The kernels that stand on it are tested where they are called
(`test_{brumby,granite,kimi,nemotron,solar}_serving.py`)."""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import slot_state
from ray_tpu.ops.slot_state import Same

rows_write = importlib.import_module("ray_tpu.ops.rows_write")

# (the leaf, the grid's axes, a grid step's own operand, the read-out): a
# slot and head's S^T with a column a sublane (retention), a slot's [N, F]
# with a row (ssm), a slot's H tiles with a row over all of them (kda)
SHAPES = {
    "retention": ((2, 3, 2, 16, 256), 2, (3, 2, 16, 1), (8, 16)),
    "ssm": ((2, 3, 16, 256), 1, (3, 1, 256), (1, 256)),
    "kda": ((2, 3, 4, 8, 128), 1, (3, 1, 128), (1, 128)),
}


def _toy(s_ref, x_ref, twice_ref, so_ref, out_ref):
    """state <- 2 state + the grid step's operand, spread over the state;
    the read-out the new state's sum, everywhere."""
    new = twice_ref[0] * s_ref[...] + x_ref[...].reshape(
        (1,) * (s_ref.ndim - x_ref.ndim) + x_ref.shape)
    so_ref[...] = new
    out_ref[...] = jnp.full(out_ref.shape, new.sum())


@pytest.mark.parametrize("kernel", list(SHAPES))
def test_an_inactive_slot_comes_back_bit_for_bit_and_one_layer_is_written(
        kernel):
    shape, grid_axes, own, read_out = SHAPES[kernel]
    ks = jax.random.split(jax.random.key(0), 2)
    state = jax.random.normal(ks[0], shape)
    x = jax.random.normal(ks[1], own)
    active = jnp.asarray([1, 0, 1])
    got, out = jax.jit(lambda s: slot_state.update(
        "toy", _toy, s, jnp.int32(1), active,
        (x, Same(jnp.full((1,), 2.0))), read_out, grid_axes=grid_axes,
        vmem_limit_bytes=2 ** 25, interpret=True))(state)
    got, out = np.asarray(got), np.asarray(out)
    grid = shape[1:1 + grid_axes]
    assert out.shape == grid + read_out
    np.testing.assert_array_equal(got[0], state[0])           # other layer
    np.testing.assert_array_equal(got[1, 1], state[1, 1])     # inactive
    assert not out[1].any()
    want = 2.0 * np.asarray(state[1]) + np.asarray(x).reshape(
        grid + (1,) * (len(shape) - 1 - len(own)) + own[grid_axes:])
    on = [0, 2]
    np.testing.assert_array_equal(got[1][on], want[on])
    sums = want.reshape(grid + (-1,)).sum(axis=-1)
    np.testing.assert_allclose(
        out[on], np.broadcast_to(sums.reshape(grid + (1,) * len(read_out)),
                                 out.shape)[on], rtol=1e-4, atol=1e-2)


def test_the_leaf_is_written_where_it_is_read():
    """`input_output_aliases` of the state onto itself, counted past the
    two prefetched scalars: a jit that donates the leaf holds no second."""
    shape, grid_axes, own, read_out = SHAPES["ssm"]
    text = jax.jit(lambda s, x: slot_state.update(
        "toy", _toy, s, jnp.int32(0), jnp.ones(3, jnp.int32),
        (x, Same(jnp.full((1,), 2.0))), read_out,
        vmem_limit_bytes=2 ** 25, interpret=False), donate_argnums=(0,)
    ).trace(jax.ShapeDtypeStruct(shape, jnp.float32),
            jax.ShapeDtypeStruct(own, jnp.float32)).jaxpr
    call, = (e for e in text.eqns if e.primitive.name == "pallas_call")
    assert call.params["input_output_aliases"] == ((2, 0),)
    assert call.params["grid_mapping"].num_index_operands == 2
    assert call.params["compiler_params"]["mosaic_tpu"].dimension_semantics \
        == ("parallel",)


@pytest.mark.parametrize("on_the_chip", [False, True],
                         ids=["plain-form", "kernels-path"])
def test_one_platform_decides_for_every_kernel_of_ops(monkeypatch,
                                                      on_the_chip):
    monkeypatch.setattr(slot_state, "on_tpu", lambda: on_the_chip)
    assert slot_state.use_kernel(None, False) == on_the_chip
    assert slot_state.use_kernel(None, True)          # interpreted: the kernel
    assert not slot_state.use_kernel(False, True)     # `kernel` says
    assert slot_state.use_kernel(True, False)
    # `rows_write.py` asks the same function
    seen = []
    monkeypatch.setattr(rows_write, "_write_kernel",
                        lambda *a: seen.append("kernel"))
    monkeypatch.setattr(rows_write, "_write_plain",
                        lambda *a: seen.append("plain"))
    rows_write.rows_write(jnp.zeros((1, 2, 1, 128, 64)), 0,
                          jnp.zeros((2, 1, 64)), jnp.zeros(2, jnp.int32),
                          jnp.ones(2, bool))
    assert seen == ["kernel" if on_the_chip else "plain"]


@pytest.mark.parametrize("module", ["power_retention", "ssm_update",
                                    "kda_update", "mla_attend", "gqa_attend",
                                    "dsa_attend", "rows_write"])
def test_no_kernel_of_ops_asks_the_platform_itself(module):
    mod = importlib.import_module(f"ray_tpu.ops.{module}")
    assert not hasattr(mod, "_on_tpu") and not hasattr(mod, "on_tpu")
    source = open(mod.__file__).read()
    assert "default_backend" not in source
    assert "pallas_call" not in source or module == "rows_write"


# ------------------------------- `rows_write`'s every-layer form (PR 58)

def _leaf_and_rows(L=4, B=4, G=5, d=64, T=256, dtype=jnp.bfloat16):
    ks = jax.random.split(jax.random.key(58), 2)
    return (jax.random.normal(ks[0], (L, B, G, d, T), dtype),
            jax.random.normal(ks[1], (L, B, G, d), dtype))


@pytest.mark.parametrize("depth", [1, 2, 4, 3],
                         ids=["a-layer", "two", "four", "three-of-four"])
@pytest.mark.parametrize("on", [[1, 1, 1, 1], [1, 0, 1, 0], [0, 0, 0, 1],
                                [0, 0, 0, 0]],
                         ids=["all", "some", "one", "none"])
def test_every_layers_row_in_one_call_is_a_call_a_layer(depth, on):
    """One call whose grid is (layers, slots) leaves the bits that a call a
    layer leaves and that the plain form leaves, however many layers a
    grid step takes (one that does not divide the layers falls back to
    their common divisor); an inactive slot's tile comes back bit for
    bit."""
    c, val = _leaf_and_rows()
    pos, on = jnp.array([0, 127, 128, 255]), jnp.array(on, bool)
    got = jax.jit(lambda c: rows_write._write_every(
        c, val, pos, on, True, depth))(c)
    want = c
    for l in range(c.shape[0]):
        want = rows_write.rows_write(want, jnp.int32(l), val[l], pos, on,
                                     interpret=True)
    plain = rows_write.rows_write(c, None, val, pos, on, kernel=False)
    for other in (want, plain):
        np.testing.assert_array_equal(np.asarray(got).view(np.uint16),
                                      np.asarray(other).view(np.uint16))
    off = ~np.asarray(on)
    np.testing.assert_array_equal(np.asarray(got)[:, off].view(np.uint16),
                                  np.asarray(c)[:, off].view(np.uint16))


def test_the_every_layer_form_is_the_entry_with_no_layer(monkeypatch):
    c, val = _leaf_and_rows(L=2, B=2, G=1)
    seen = []
    monkeypatch.setattr(rows_write, "_write_every",
                        lambda *a: seen.append(a[-1]) or a[0])
    rows_write.rows_write(c, None, val, jnp.zeros(2, jnp.int32),
                          jnp.ones(2, bool), interpret=True)
    assert seen == [True]
    # a head of 128 lanes, positions on the rows, has no such form
    with pytest.raises(AssertionError):
        rows_write.rows_write(jnp.swapaxes(c, 3, 4), None, val,
                              jnp.zeros(2, jnp.int32), jnp.ones(2, bool))


def test_the_every_layer_form_writes_the_leaf_where_it_reads_it():
    c, val = _leaf_and_rows()
    text = jax.jit(lambda c, val: rows_write._write_every(
        c, val, jnp.zeros(4, jnp.int32), jnp.ones(4, bool), False, 2),
        donate_argnums=(0,)).trace(c, val).jaxpr
    call, = (e for e in text.eqns if e.primitive.name == "pallas_call")
    assert call.params["input_output_aliases"] == ((2, 0),)
    mapping = call.params["grid_mapping"]
    assert mapping.num_index_operands == 2 and mapping.grid == (2, 4)
    # the rows go in a head a lane: [L, B, d, G], 16 KB a grid step in HBM
    # where [G, d, 1] would be a tile of its own a head
    assert [tuple(d.block_size for d in b.block_shape)
            for b in mapping.block_mappings] == [
        (2, 1, 5, 64, 128), (2, 1, 64, 5), (2, 1, 5, 64, 128)]
