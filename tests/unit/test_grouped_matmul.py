"""The Pallas grouped matmul (``ops/pallas/grouped_matmul.py``),
interpreted at small sizes: held row for row to ``jax.lax.ragged_dot``
and to a plain per-row product, and ``moe/sharded_moe.py
grouped_matmul``'s choice between the two arms. That Mosaic compiles it
at a cell's widths, one ``ragged-dot-grouped`` call a matmul, is held in
``tests/unit/test_paged_pool_in_place.py`` (the one file that describes
a TPU)."""

import contextlib

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from deepspeed_tpu.comm.mesh import MeshSpec, build_mesh, global_mesh_scope
from deepspeed_tpu.moe import DroplessMoE, sharded_moe
from deepspeed_tpu.observability.metrics import get_registry
from deepspeed_tpu.ops.pallas import grouped_matmul as gm
from deepspeed_tpu.ops.pallas import tuning

K, N = 128, 256


def _operands(m, sizes, seed=0, k=K, n=N):
    rng = np.random.default_rng(seed)
    rows = jnp.asarray(rng.standard_normal((m, k)), jnp.bfloat16)
    w = jnp.asarray(rng.standard_normal((len(sizes), k, n)) / 8,
                    jnp.bfloat16)
    return rows, w, jnp.asarray(sizes, jnp.int32)


def _by_row(rows, w, sizes):
    """Row i times its group's matrix in float64; a row in no group is
    zero."""
    rows, w = np.asarray(rows, np.float64), np.asarray(w, np.float64)
    out = np.zeros((rows.shape[0], w.shape[-1]))
    lo = 0
    for g, size in enumerate(np.asarray(sizes)):
        out[lo:lo + size] = rows[lo:lo + size] @ w[g]
        lo += size
    return out


def _kernel(rows, w, sizes, out=None, bm=16, bn=None):
    key = gm.blocks(rows.shape[0], rows.shape[1], w.shape[-1], w.shape[0],
                    rows.dtype, out or rows.dtype)[2]
    with tuning.tuning_table({key: {"block_m": bm,
                                    "block_n": bn or w.shape[-1]}}):
        return jax.jit(lambda *a: gm.grouped_matmul(*a, out))(rows, w, sizes)


CASES = {
    "empty-groups-between-full-ones": (64, [16, 0, 0, 32, 0, 16]),
    "a-group-straddling-three-tiles": (64, [5, 40, 3, 16]),
    "rows-past-the-groups-sum": (64, [3, 0, 7, 9]),
    "whole-tiles-past-the-sum": (96, [10, 0, 12]),
    "m-no-multiple-of-the-tile": (72, [30, 0, 42]),
    "m-no-multiple-and-rows-past-the-sum": (40, [0, 13, 9]),
    "one-group-holds-every-row": (64, [0, 64, 0]),
    "the-last-group-holds-every-row": (48, [0, 0, 48]),
    "no-group-holds-a-row": (32, [0, 0, 0, 0]),
    "a-row-a-group": (32, [1] * 32),
}


@pytest.mark.parametrize("out", [None, jnp.float32],
                         ids=["bf16-out", "float32-out"])
@pytest.mark.parametrize("m,sizes", CASES.values(), ids=CASES.keys())
def test_the_kernel_is_ragged_dot_row_for_row(m, sizes, out):
    rows, w, groups = _operands(m, sizes)
    got = _kernel(rows, w, groups, out)
    want = jax.lax.ragged_dot(rows, w, groups, preferred_element_type=out)
    assert got.dtype == want.dtype and got.shape == want.shape
    # bf16 x bf16 products summed in float32 along k on both sides: the
    # order of a float32 sum is all that may differ, and where a sum lies
    # on the edge of a bf16 step, the step it is rounded to
    want = np.asarray(want, np.float32)
    tol = 2e-5 if out == jnp.float32 else 2.0 ** -8 * np.abs(want).max()
    assert np.abs(np.asarray(got, np.float32) - want).max() <= tol
    assert np.mean(np.asarray(got, np.float32) != want) < \
        (1.0 if out == jnp.float32 else 0.01)
    exact = _by_row(rows, w, sizes)
    step = 2.0 ** -8 if out is None else 1e-5      # a bf16 result's rounding
    assert np.abs(np.asarray(got, np.float64) - exact).max() \
        <= step * max(1.0, np.abs(exact).max())


@pytest.mark.parametrize("bm,bn", [(16, 128), (32, 256), (64, 128),
                                   (128, 256)])
def test_any_tile_gives_the_same_rows(bm, bn):
    m, sizes = 128, [7, 0, 50, 1, 0, 33, 20]
    rows, w, groups = _operands(m, sizes, seed=1)
    got = _kernel(rows, w, groups, jnp.float32, bm=bm, bn=bn)
    want = jax.lax.ragged_dot(rows, w, groups,
                              preferred_element_type=jnp.float32)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() <= 2e-5


@pytest.mark.parametrize("layer", [0, 2, 3])
def test_a_stack_of_layers_walks_one_layers_groups_only(layer):
    """``[L·E]`` groups of which one layer's hold rows: every visit of
    the walk — the real ones, those of the rows in no group, and the
    steps past the last that a grid of a static length makes — names a
    matrix of that layer, so no other layer's weights are ever
    fetched."""
    experts, layers, m, bm = 8, 4, 64, 16
    own = np.array([9, 0, 0, 20, 1, 0, 17, 0])
    sizes = np.zeros(layers * experts, np.int32)
    sizes[layer * experts:(layer + 1) * experts] = own
    n, group, tile, lo, hi = jax.jit(
        lambda g: gm.visits(g, m, bm))(jnp.asarray(sizes))
    group, tile = np.asarray(group), np.asarray(tile)
    assert group.shape == (m // bm + min(len(sizes), m),)
    touched = set(layer * experts + np.flatnonzero(own))
    assert set(group.tolist()) == touched
    # tiles in order, each group's visits together: 9 | 20 | 1 | 17 rows
    # lie in tiles 0 | 0-1 | 1 | 1-2, and tile 3 holds rows of no group
    assert int(n) == 7
    assert tile[:7].tolist() == [0, 0, 1, 1, 1, 2, 3]
    assert (tile[7:] == 3).all() and (np.asarray(hi)[6:] == 0).all()
    assert np.asarray(lo)[:6].tolist() == [0, 9, 9, 29, 30, 30]
    assert np.asarray(hi)[:6].tolist() == [9, 29, 29, 30, 47, 47]
    rows, w, groups = _operands(m, sizes, seed=2)
    want = jax.lax.ragged_dot(rows, w, groups,
                              preferred_element_type=jnp.float32)
    got = _kernel(rows, w, groups, jnp.float32)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() <= 2e-5


@pytest.fixture
def as_on_one_tpu(monkeypatch):
    """``sharded_moe.grouped_matmul`` chooses as it does on a TPU; the
    kernel itself still sees the CPU and is interpreted."""
    monkeypatch.setattr(sharded_moe, "on_tpu", lambda: True)
    tuning.clear_last_dispatch()
    jax.clear_caches()
    yield
    jax.clear_caches()


def _traced(arm):
    return get_registry().counter(f"moe/grouped_matmul_traced/{arm}").value


def test_float32_rows_go_as_three_terms_through_the_kernel(as_on_one_tpu):
    """float32 rows over bfloat16 weights: three bf16 terms a row, side
    by side in its group, one kernel call, within 1e-6 of float64."""
    m, sizes = 32, [5, 0, 12, 9]
    rng = np.random.default_rng(3)
    rows = jnp.asarray(rng.standard_normal((m, K)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((4, K, N)) / 8, jnp.bfloat16)
    groups = jnp.asarray(sizes, jnp.int32)
    before = _traced("kernel"), _traced("xla")
    with jax.default_matmul_precision("highest"):
        program = jax.make_jaxpr(sharded_moe.grouped_matmul)(rows, w, groups)
        got = jax.jit(sharded_moe.grouped_matmul)(rows, w, groups)
    assert str(program).count("pallas_call") == 1
    assert "ragged_dot" not in str(program)
    # per trace, as ``comm/traced_calls/*`` are: never per call
    assert _traced("kernel") > before[0] and _traced("xla") == before[1]
    exact = _by_row(rows, w, sizes)
    assert np.abs(np.asarray(got, np.float64) - exact).max() \
        <= 1e-6 * np.abs(exact).max()
    record, = tuning.last_dispatch("grouped_matmul").values()
    assert record["impl"] == "kernel" and record["interpret"] is True
    assert (record["block_m"], record["block_n"]) == (96, N)
    assert record["key"] == \
        f"grouped_matmul/groups4/sq96_sk{K}_d{N}_bfloat16_full"


@pytest.mark.parametrize("why,rows_dtype,w_dtype,m,k,mesh", [
    ("float32 rows over float32", jnp.float32, jnp.float32, 32, K, None),
    ("no multiple of 128", jnp.bfloat16, jnp.bfloat16, 32, 96, None),
    ("fewer than 16", jnp.bfloat16, jnp.bfloat16, 8, K, None),
    ("over 2 devices", jnp.bfloat16, jnp.bfloat16, 32, K, 2),
], ids=["float32-operands", "k-unaligned", "few-rows", "two-devices"])
def test_what_the_kernel_refuses_goes_to_ragged_dot(
        as_on_one_tpu, why, rows_dtype, w_dtype, m, k, mesh):
    rows = jnp.ones((m, k), rows_dtype)
    w = jnp.ones((3, k, N), w_dtype)
    groups = jnp.asarray([m // 2, 0, m // 4], jnp.int32)
    scope = contextlib.nullcontext() if mesh is None else global_mesh_scope(
        jax.sharding.Mesh(np.array(jax.devices()[:mesh]), ("data",)))
    before = _traced("xla")
    with scope:
        program = str(jax.make_jaxpr(sharded_moe.grouped_matmul)(
            rows, w, groups))
    assert "ragged_dot" in program and "pallas_call" not in program
    assert _traced("xla") == before + 1
    record, = tuning.last_dispatch("grouped_matmul").values()
    assert record["impl"] == "ragged_dot" and why in record["reason"]


def test_off_the_tpu_the_grouped_matmul_is_ragged_dot():
    rows, w, groups = _operands(32, [10, 0, 22])
    before = _traced("xla"), _traced("kernel")
    program = str(jax.make_jaxpr(sharded_moe.grouped_matmul)(rows, w, groups))
    assert "ragged_dot" in program and "pallas_call" not in program
    assert (_traced("xla"), _traced("kernel")) == (before[0] + 1, before[1])
    record, = tuning.last_dispatch("grouped_matmul").values()
    assert record["reason"] == "not on a TPU"


def _layer_and_operands():
    layer = DroplessMoE(num_experts=4, num_experts_per_tok=2,
                        dtype=jnp.bfloat16)
    keys = jax.random.split(jax.random.PRNGKey(0), 5)
    x = jax.random.normal(keys[0], (2, 8, K))
    stacks = tuple((jax.random.normal(key, shape) / 8).astype(jnp.bfloat16)
                   for key, shape in zip(keys[1:4], [
                       (2, 4, K, N), (2, 4, K, N), (2, 4, N, K)]))
    params = layer.init(keys[4], x, experts=stacks, layer=1)["params"]

    def loss(params, x, stacks):
        out, _ = layer.apply({"params": params}, x, experts=stacks, layer=1)
        return jnp.sum(out.astype(jnp.float32) ** 2)
    return loss, (params, x, stacks)


def test_the_gradient_of_a_dropless_layer_is_ragged_dots(monkeypatch):
    """A ``DroplessMoE`` trains through ``ragged_dot``'s own rule: off
    the TPU nothing else is traced, and where the forward takes the
    kernel the backward is the ``ragged_dot`` arm's, so the gradients
    are the same arrays either way."""
    loss, args = _layer_and_operands()
    grad = jax.grad(loss, argnums=(0, 1, 2))
    program = str(jax.make_jaxpr(grad)(*args))
    assert "ragged_dot" in program and "pallas_call" not in program
    want = grad(*args)

    monkeypatch.setattr(sharded_moe, "on_tpu", lambda: True)
    jax.clear_caches()                 # the trace above is not this one
    program = str(jax.make_jaxpr(grad)(*args))
    # the forward's three alone (gate and up are one jitted function,
    # printed once and called twice)
    assert program.count("name=_call") == 3
    assert program.count("pallas_call[") == 2
    got = jax.jit(grad)(*args)
    jax.clear_caches()
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert a.shape == b.shape
        assert np.abs(a - b).max() <= 1e-2 * max(1e-6, np.abs(b).max())


def test_a_served_olmoe_gives_the_same_tokens_through_either_arm(
        monkeypatch):
    """The serving programs (chunk prefill of 256 and 128-row calls,
    paged decode of 16) over a bf16 OLMoE two layers deep: with the
    kernel in every expert matmul (interpreted) the served tokens are
    those of the ``ragged_dot`` arm."""
    import flax.core.meta as flax_meta
    import deepspeed_tpu as ds
    from deepspeed_tpu.models.olmoe import OLMoE, OLMoEConfig
    model = OLMoE(OLMoEConfig(
        hidden_size=K, intermediate_size=K, num_hidden_layers=2,
        num_attention_heads=4, num_experts=4, num_experts_per_tok=2,
        vocab_size=256, max_position_embeddings=512, dtype=jnp.bfloat16,
        param_dtype=jnp.bfloat16))
    params = flax_meta.unbox(model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))["params"]
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, 256, size=n, dtype=np.int32)
               for n in (150, 40, 9)]

    # one device, as a cell's server has: over the suite's eight the
    # grouped matmul stays ``ragged_dot`` (a Mosaic call is not
    # partitioned)
    one = build_mesh(MeshSpec(), devices=jax.devices()[:1], set_global=False)

    def served():
        srv = ds.init_inference(model, params=params, dtype=jnp.bfloat16,
                                mesh=one).serve(
            {"num_slots": 8, "max_len": 512,
             "paging": {"page_len": 128, "num_pages": 33}})
        handles = [srv.submit(p, max_new_tokens=6) for p in prompts]
        srv.run()
        srv.close()
        return [list(h.output_tokens) for h in handles]

    want = served()
    monkeypatch.setattr(sharded_moe, "on_tpu", lambda: True)
    jax.clear_caches()
    tuning.clear_last_dispatch()
    before = _traced("kernel"), _traced("xla")
    got = served()
    jax.clear_caches()
    assert got == want
    assert _traced("kernel") > before[0] and _traced("xla") == before[1]
    record = tuning.last_dispatch("grouped_matmul")["groups8"]
    assert record["impl"] == "kernel"
