"""The ring's write (``ops/pallas/ring_append.py``), interpreted: bit for
bit what a numpy loop over the live rows leaves, and nothing else touched.

Small shapes: the kernel's control flow (the rows' order, two buffers,
the last writes' waits) does not depend on the widths, and Mosaic's view
of the real ones is ``test_paged_pool_in_place.py``'s.
"""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from deepspeed_tpu.models import layers
from deepspeed_tpu.ops.pallas import ring_append as ra
from deepspeed_tpu.ops.pallas import tuning

ROWS, HEADS, D, WINDOW = 6, 3, 8, 512
# the ring's first and last lane, the two sides of a tile's edge, and two
# rows (2 and 4) whose tokens lie in one tile of lanes
LANES = (0, 127, 128, 511, 130, 300)
LIVE = {"all": (1, 1, 1, 1, 1, 1), "none": (0, 0, 0, 0, 0, 0),
        "scattered": (0, 1, 0, 0, 1, 1), "one-tile": (0, 0, 1, 0, 1, 0),
        "last-row": (0, 0, 0, 0, 0, 1)}


def _operands(ring_dtype, col_dtype, window=WINDOW, seed=0):
    rng = np.random.default_rng(seed)
    draw = lambda shape, dtype: jnp.asarray(
        rng.standard_normal(shape), dtype)
    shape = (ROWS, HEADS, D, window)
    # the keys' ring and the values' hold different things, and so do
    # their columns: a write that crossed them would show
    rings = draw(shape, ring_dtype), draw(shape, ring_dtype)
    cols = draw(shape[:3] + (1,), col_dtype), draw(shape[:3] + (1,),
                                                   col_dtype)
    return rings, cols


def _reference(rings, cols, lanes, live):
    out = []
    for ring, col in zip(rings, cols):
        want = np.array(ring)
        new = np.array(col.astype(ring.dtype))
        for b in np.flatnonzero(live):
            want[b, :, :, lanes[b]] = new[b, :, :, 0]
        out.append(want)
    return out


@pytest.mark.parametrize("live", list(LIVE), ids=list(LIVE))
@pytest.mark.parametrize("ring_dtype,col_dtype", [
    (jnp.bfloat16, jnp.float32), (jnp.bfloat16, jnp.bfloat16),
    (jnp.float32, jnp.float32)], ids=["bf16-from-f32", "bf16", "f32"])
def test_the_write_is_the_numpy_loops_bit_for_bit(ring_dtype, col_dtype,
                                                  live):
    rings, cols = _operands(ring_dtype, col_dtype)
    mask = np.asarray(LIVE[live], bool)
    got = jax.jit(layers._ring_append)(rings, cols, jnp.asarray(LANES),
                                       jnp.asarray(mask))
    want = _reference(rings, cols, LANES, mask)
    for g, w, ring in zip(got, want, rings):
        assert g.dtype == ring.dtype
        np.testing.assert_array_equal(np.array(g), w)
    # a row that does not decode, and every lane but the token's of one
    # that does, is the input's
    for g, ring in zip(got, rings):
        g, ring = np.array(g), np.array(ring)
        assert np.array_equal(g[~mask], ring[~mask])
        for b in np.flatnonzero(mask):
            others = np.arange(WINDOW) != LANES[b]
            assert np.array_equal(g[b][..., others], ring[b][..., others])


@pytest.mark.parametrize("window,lanes", [
    (32, (0, 31, 5, 5, 16, 17)), (128, (0, 127, 64, 64, 1, 126)),
    (256, (0, 127, 128, 255, 129, 3))], ids=["w32", "w128", "w256"])
def test_a_ring_of_one_tile_or_of_several(window, lanes):
    """A window under 128 lanes is its own tile (the unit tests' models
    keep 32); every row live, twice over so that a second call writes
    onto the first call's result."""
    rings, cols = _operands(jnp.float32, jnp.float32, window)
    live = jnp.ones(ROWS, bool)
    write = jax.jit(layers._ring_append)
    once = write(rings, cols, jnp.asarray(lanes), live)
    again = tuple(-c for c in cols)
    nxt = tuple((l + 1) % window for l in lanes)
    twice = write(once, again, jnp.asarray(nxt), live)
    want = _reference(rings, cols, lanes, np.ones(ROWS, bool))
    want = _reference([jnp.asarray(w) for w in want], again, nxt,
                      np.ones(ROWS, bool))
    for g, w in zip(twice, want):
        np.testing.assert_array_equal(np.array(g), w)


def test_a_window_that_is_no_multiple_of_128_is_refused_by_name():
    rings, cols = _operands(jnp.float32, jnp.float32, 192)
    with pytest.raises(ValueError, match="ring of 192 tokens"):
        layers._ring_append(rings, cols, jnp.zeros(ROWS, jnp.int32),
                            jnp.ones(ROWS, bool))


def test_one_call_named_ring_append_and_no_loop_around_it():
    """The write is one Pallas call for both rings, under a name of its
    own (a device trace's ``%ring_append.N``: the ring kernel's metric
    reads ``%window_attn*``), with the rings aliased to its results; the
    dispatch record says what ran."""
    rings, cols = _operands(jnp.bfloat16, jnp.bfloat16)
    tuning.clear_last_dispatch()
    # (a function of this test's own: the tests above have traced
    # ``_ring_append`` at these shapes, and a cached trace records nothing)
    jaxpr = jax.make_jaxpr(lambda *a: layers._ring_append(*a))(
        rings, cols, jnp.asarray(LANES), jnp.ones(ROWS, bool))

    def primitives(jpr):
        for eqn in jpr.eqns:
            yield eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                if eqn.primitive.name != "pallas_call":
                    yield from primitives(sub)

    eqns = list(primitives(jaxpr.jaxpr))
    calls = [e for e in eqns if e.primitive.name == "pallas_call"]
    assert len(calls) == 1
    call, = calls
    assert call.params["name"] == ra.TRACE_NAME == "ring_append"
    assert dict(call.params["input_output_aliases"]) == {5: 0, 6: 1}
    assert not [e for e in eqns if e.primitive.name in ("while", "scan")]
    rec, = tuning.last_dispatch(ra.KERNEL).values()
    assert (rec["impl"], rec["key"], rec["tile"], rec["interpret"]) == (
        "kernel", f"b{ROWS}_h{HEADS}_d{D}_w{WINDOW}_bfloat16", 128, True)
