"""The paged decode program, compiled for a described v5e at the serve
cell's widths: what the chip's compiler does with the page pool.

Layouts are the TPU's here, which the CPU suite cannot see: the parent's
scatter over the page and the in-page offset compiled to four copies of
the whole pool (a layout change and back, K and V) and 4 GB of scratch.
Nothing runs — no time comes out of this file — but a program that
slices, restacks or relays out the pool does not pass it. The topology
is described inside a fixture and only here, so under xdist one worker
loads the TPU's compiler (on-chip-measurement guide, section 2).
"""

import importlib
import os
import re

import pytest
import jax
import jax.numpy as jnp

from deepspeed_tpu.models.gpt import GPT, GPTConfig
from deepspeed_tpu.models.olmoe import OLMoE, OLMoEConfig
from deepspeed_tpu.inference.cache import (init_page_pool,
                                           quantize_page_pool)
from deepspeed_tpu.serving.paging.manager import _paged_decode_iter_impl

# the serve cell (benchmarks/chip/configs/gpt2-1.3b-serve.json) but for
# its depth: two layers compile in seconds and hold every pool operation
WIDTH = dict(vocab_size=50257, max_seq_len=2048, d_model=2048, n_heads=16)
LAYERS, SLOTS, PAGES, PAGE_LEN, MAX_PAGES = 2, 32, 321, 128, 16
# the OLMoE cell (configs/olmoe-1b-7b-8l-serve.json) at its published
# widths, two layers deep: 64 experts of 1024, 8 a token
OLMOE_WIDTH = dict(vocab_size=50304, hidden_size=2048, intermediate_size=1024,
                   num_attention_heads=16, num_experts=64,
                   num_experts_per_tok=8, max_position_embeddings=4096)

# instructions that may carry a pool-shaped value without moving it: no
# ``copy``, and no ``conditional`` whose branches would each carry it
IN_PLACE = {"parameter", "get-tuple-element", "tuple", "bitcast", "while",
            "dynamic-update-slice"}
# ``temp_before``: what each program took of scratch before the append's
# trips followed the rows that decode (PR 31's tree, same compiler). Ordering
# those rows and counting them adds vectors of 32 numbers: one to three
# padded buffers of 32,256 bytes (the unscanned program's scratch fell
# by 830,464). The smallest thing of the pool's shape, one page of one
# layer, is 512 KiB (256 KiB int8), and a trip moves one of every layer
SMALL_VECTORS = 128 * 1024


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:            # no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def hlo_has(compiled, name):
    return name in compiled.as_text()


def _computations(hlo):
    """{computation: its lines} of an optimized module."""
    comps, name = {}, None
    for line in hlo.splitlines():
        head = re.match(r"(?:ENTRY )?%([\w.\-]+) \(.*\{$", line)
        if head:
            name = head.group(1)
            comps[name] = []
        elif name:
            comps[name].append(line)
    return comps


def _roots(comps):
    """{computation: opcode of its ROOT}."""
    roots = {}
    for name, lines in comps.items():
        for line in lines:
            root = re.match(r"\s+ROOT %[\w.\-]+ = \S+ ([\w\-]+)\(", line)
            if root:
                roots[name] = root.group(1)
    return roots


# equations a value passes through unchanged but for its shape or type
PASS_THROUGH = {"broadcast_in_dim", "convert_element_type", "reshape",
                "squeeze", "copy"}


def _sub_jaxprs(eqn):
    for v in eqn.params.values():
        for sub in (v if isinstance(v, (tuple, list)) else (v,)):
            inner = getattr(sub, "jaxpr", sub)
            if hasattr(inner, "eqns"):
                yield inner


def _equations(frames, primitive):
    """Every ``primitive`` equation under ``frames[-1]`` with the frames
    it sits in, outermost first: ``(jaxpr, the equation of the jaxpr
    before it that holds it)``, the program's own being ``(program,
    None)``."""
    for eqn in frames[-1][0].eqns:
        if eqn.primitive.name == primitive:
            yield eqn, frames
        for sub in _sub_jaxprs(eqn):
            yield from _equations(frames + ((sub, eqn),), primitive)


def _origin(var, frames):
    """Where ``var`` of the jaxpr ``frames[-1]`` comes from: ``("input",
    i)`` (the i-th argument of the program), ``("literal", value)``, or
    ``(equation, frames)`` for the first equation behind it that is more
    than a change of shape or type. A call's operands line up with its
    jaxpr's (scan: constants, carry, xs; jit and closed calls: one for
    one)."""
    while True:
        if hasattr(var, "val"):
            return "literal", var.val
        jaxpr, call = frames[-1]
        made = next((e for e in jaxpr.eqns if var in e.outvars), None)
        if made is None:                          # an argument of this jaxpr
            at = jaxpr.invars.index(var)
            if call is None:
                return "input", at
            assert len(call.invars) == len(jaxpr.invars), call.primitive
            var, frames = call.invars[at], frames[:-1]
        elif made.primitive.name in PASS_THROUGH:
            var = made.invars[0]
        elif made.primitive.name in ("jit", "pjit", "closed_call"):
            sub = next(_sub_jaxprs(made))
            var = sub.outvars[made.outvars.index(var)]
            frames = frames + ((sub, made),)
        else:
            return made, frames


def _decode_operands(model, one_chip, kv_int8=False):
    """``(args, static, pool_shapes)`` of ``_paged_decode_iter_impl`` for
    ``model`` at the cell's slots and pages: shapes placed on the
    described chip — nothing is allocated anywhere."""
    import flax.core.meta as flax_meta
    params = jax.eval_shape(
        lambda r: flax_meta.unbox(model.init(
            r, jnp.ones((1, 8), jnp.int32)))["params"],
        jax.random.PRNGKey(0))

    def pool():
        p = init_page_pool(model, params, PAGES, PAGE_LEN)
        return quantize_page_pool(p) if kv_int8 else p

    def on_chip(tree):
        """``tree``'s shapes (of a thunk: of what it would build)."""
        shapes = jax.eval_shape(tree) if callable(tree) else tree
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one_chip), shapes)

    slot = lambda dtype: jax.ShapeDtypeStruct((SLOTS,), dtype)
    state = {"lengths": slot(jnp.int32), "last_token": slot(jnp.int32),
             "active": slot(jnp.bool_), "remaining": slot(jnp.int32)}
    pool_shapes = on_chip(pool)
    # params, pool, page table, state, rng, iteration; then the statics
    args = (on_chip(params), pool_shapes,
            on_chip(jax.ShapeDtypeStruct((SLOTS, MAX_PAGES), jnp.int32)),
            on_chip(state), on_chip(lambda: jax.random.PRNGKey(0)),
            on_chip(jax.ShapeDtypeStruct((), jnp.int32)))
    static = (50256, 1.0, 0, 1.0, None, True, False, False, True,
              jnp.bfloat16)
    return args, static, pool_shapes


def _compile_decode(model, args, static):
    return jax.jit(
        _paged_decode_iter_impl, static_argnums=(0, 11, 12, 13, 14, 15, 16),
        donate_argnums=(2, 4)).lower(model, *args, *static).compile()


# ---------------------------------------------------------------------------
# the paged kernel alone at bf16 shapes whose grid steps hold more than
# eight query-head rows: grouped heads (PR 49), sixteen ungrouped (PR 51).
# Mosaic does not raise on a slice it cannot lay out, it aborts the
# process: each compile runs in a child with a time limit, so an abort is
# one failed test and not a dead worker. These stand first in the file:
# the child has to load the TPU's compiler, which one process at a time
# may (the worker loads it for the tests below)
# ---------------------------------------------------------------------------

_COMPILE_PAGED = """
import json, os, sys
os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, sys.argv[1])
slots, heads, kv, d, layers, max_pages = map(int, sys.argv[2:8])
import importlib
import jax, jax.numpy as jnp
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding
try:
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
except Exception as e:
    print(json.dumps({"skip": str(e)[:300]}))
    sys.exit(0)
from deepspeed_tpu.ops.pallas import tuning
pa = importlib.import_module("deepspeed_tpu.ops.pallas.paged_attention")
pa._interpret = lambda: False         # through Mosaic, as on the chip
chip = SingleDeviceSharding(topo.devices[0])
S = lambda shape, t=jnp.bfloat16: jax.ShapeDtypeStruct(shape, t,
                                                       sharding=chip)
pool = S((layers, slots * max_pages + 1, kv, d, 128))
new = S((slots, kv, d, 1))
compiled = jax.jit(lambda q, kp, vp, table, lens, kn, vn, i:
                   pa.paged_attention(q, kp, vp, table, lens, kn, vn, layer=i,
                                      impl="kernel")).lower(
    S((slots, 1, heads, d)), pool, pool, S((slots, max_pages), jnp.int32),
    S((slots,), jnp.int32), new, new, S((), jnp.int32)).compile()
rec = tuning.last_dispatch("paged_attention")["page128"]
print(json.dumps({"record": rec, "mosaic_calls": compiled.as_text().count(
    'custom_call_target="tpu_custom_call"')}))
"""


@pytest.mark.parametrize("shape,head_block,rows,source", [
    # serve-falconh1-chat: 64 rows, 20 query heads on 4 K/V heads of 128, a
    # stacked pool of 9 layers, 16 pages a row: one step a row, the swept
    # block of ``flash_tuning_defaults.json``
    ((64, 20, 4, 128, 9, 16), 4, 20, "defaults"),
    # LFM2's heads were its pool bf16, and five on each of eight: the most
    # rows a step any geometry here makes (a group is at most eight)
    ((32, 32, 8, 64, 2, 32), 8, 32, "constants"),
    ((64, 40, 8, 128, 2, 16), 8, 40, "defaults"),
    # serve-olmoe-longgen (a stacked pool of 8 layers) and the two
    # serve-1p3b cells (24): 32 rows, sixteen ungrouped heads of 128, all of
    # them one grid step at the table's block (PR 51)
    ((32, 16, 16, 128, 8, 16), 16, 16, "defaults"),
    ((32, 16, 16, 128, 24, 16), 16, 16, "defaults"),
    # serve-phi4flash-reason: 64 rows, differential attention's 40 query
    # rows on ten cached heads of 128 (two halves of 64 stacked), one
    # layer's pool of 32 pages a row: all ten heads one grid step, 40
    # rows, at the table's block of one page (PR 58; two a step before)
    ((64, 40, 10, 128, 1, 32), 10, 40, "defaults"),
    # the same heads at another slot count, where no entry asks: two
    ((32, 40, 10, 128, 1, 32), 2, 8, "constants"),
], ids=["falcon-h1-20on4", "32on8-d64", "40on8", "olmoe-16", "gpt2-1p3b-16",
        "phi4flash-40on10", "40on10-unswept"])
def test_bf16_steps_of_many_rows_compile_for_the_chip(
        shape, head_block, rows, source):
    import json
    import subprocess
    import sys
    repo = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    try:
        child = subprocess.run(
            [sys.executable, "-c", _COMPILE_PAGED, repo, *map(str, shape)],
            capture_output=True, text=True, timeout=300)
    except subprocess.TimeoutExpired:
        pytest.fail(f"compiling the paged kernel at {shape} hung")
    if child.returncode and "libtpu_lockfile" in child.stderr:
        pytest.skip("another process holds the TPU's compiler")
    assert child.returncode == 0, (
        f"the compile at {shape} ended the process with "
        f"{child.returncode}: {child.stderr[-600:]}")
    said = json.loads(child.stdout.strip().splitlines()[-1])
    if "skip" in said:
        pytest.skip(f"no v5e:2x2 topology can be described here: "
                    f"{said['skip']}")
    rec = said["record"]
    assert (rec["impl"], rec["products"]) == ("kernel", "bfloat16")
    assert (rec["head_block"], rec["rows"]) == (head_block, rows)
    assert rec["source"] == source
    assert said["mosaic_calls"] == 1


@pytest.mark.parametrize("scan_layers,kv_int8,experts,temp_before", [
    (True, False, False, 742400), (False, False, False, 2734080),
    (True, True, False, 935936), (True, False, True, 4657664)],
    ids=["scanned-bf16", "unscanned-bf16", "scanned-int8", "olmoe-bf16"])
def test_decode_program_leaves_the_pool_where_it_is(one_chip, monkeypatch,
                                                    scan_layers, kv_int8,
                                                    experts, temp_before):
    # the kernel must lower through Mosaic as on the chip: this process'
    # platform is the CPU, where it would be interpreted
    monkeypatch.setattr(
        importlib.import_module("deepspeed_tpu.ops.pallas.paged_attention"),
        "_interpret", lambda: False)
    if experts:
        model = OLMoE(OLMoEConfig(num_hidden_layers=LAYERS,
                                  dtype=jnp.bfloat16,
                                  param_dtype=jnp.bfloat16, **OLMOE_WIDTH))
    else:
        model = GPT(GPTConfig(n_layers=LAYERS, scan_layers=scan_layers,
                              dtype=jnp.bfloat16, param_dtype=jnp.bfloat16,
                              **WIDTH))
    args, static, pool_shapes = _decode_operands(model, one_chip, kv_int8)
    compiled = _compile_decode(model, args, static)

    # the lengths the Mosaic call walks are masked by ``active``: a row
    # that does not decode is handed 0, whatever length it still holds
    program = jax.make_jaxpr(lambda *a: _paged_decode_iter_impl(
        model, *a, *static))(*args).jaxpr
    paths = [jax.tree_util.keystr(path) for path, _ in
             jax.tree_util.tree_flatten_with_path(args)[0]]
    active_at, = [i for i, k in enumerate(paths) if "active" in k]
    lengths_at, = [i for i, k in enumerate(paths) if "lengths" in k]
    calls = list(_equations(((program, None),), "pallas_call"))
    assert len(calls) == (1 if scan_layers else LAYERS)
    for call, frames in calls:
        # scalar prefetch: lengths is the kernel's first operand
        select, at = _origin(call.invars[0], frames)
        assert select.primitive.name == "select_n", select
        which, idle, decoding = select.invars
        assert _origin(which, at) == ("input", active_at)
        assert _origin(idle, at) == ("literal", 0)
        clamp, at = _origin(decoding, at)               # min(lengths, S - 1)
        assert clamp.primitive.name == "min", clamp
        assert ("input", lengths_at) in [_origin(v, at)
                                         for v in clamp.invars]

    kv = [x for x in jax.tree.leaves(pool_shapes) if x.ndim >= 4]
    # the append: one loop a pool leaf, the leaf its carry, and as many
    # trips as rows decode — the bound is no constant, it is how many
    # pages are not the null page that ``active`` routes the others to
    loops = [(eqn, frames) for eqn, frames in
             _equations(((program, None),), "while")
             if any(v.aval.shape == x.shape for v in eqn.outvars for x in kv)]
    assert len(loops) == len(kv)
    for loop, frames in loops:
        consts = loop.params["cond_nconsts"] + loop.params["body_nconsts"]
        lower, upper = loop.invars[consts], loop.invars[consts + 1]
        assert _origin(lower, frames) == ("literal", 0)
        summed, at = _origin(upper, frames)
        assert summed.primitive.name == "reduce_sum", summed
        written, at = _origin(summed.invars[0], at)
        assert written.primitive.name == "ne", written
        assert _origin(written.invars[1], at) == ("literal", 0)   # NULL_PAGE
        routed, at = _origin(written.invars[0], at)
        assert routed.primitive.name == "select_n", routed
        assert _origin(routed.invars[0], at) == ("input", active_at)

    pool_bytes = sum(x.size * x.dtype.itemsize for x in kv)
    key = next(x for x in kv if x.shape[-2] == 128)
    layer_k_bytes = (key.size * key.dtype.itemsize
                     // (LAYERS if scan_layers else 1))
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= pool_bytes
    assert mem.temp_size_in_bytes < layer_k_bytes, (
        f"{mem.temp_size_in_bytes} bytes of scratch: some of the pool is "
        "copied")
    assert mem.temp_size_in_bytes <= temp_before + SMALL_VECTORS, (
        f"{mem.temp_size_in_bytes} bytes of scratch against {temp_before} "
        "before: more than vectors of a number a row")
    if experts:
        # the dropless dispatch sorts 32 x 8 rows: its scratch is rows of
        # activations, nowhere near a capacity gate's [T, E, C] one-hot,
        # one expert's weights (12 MB) or a copy of a layer's 805 MB
        assert mem.temp_size_in_bytes < 8 * 2 ** 20, mem.temp_size_in_bytes
        assert hlo_has(compiled, "ragged-dot")

    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo            # the Mosaic kernel is there
    comps = _computations(hlo)
    roots = _roots(comps)
    # the pool, a layer's slice of it, and the same of the scale planes
    moved = re.compile(r"\[(?:\d+,)?%d,16,(?:128|1),%d\]" % (PAGES, PAGE_LEN))
    for line in hlo.splitlines():
        m = re.match(r"\s*(?:ROOT )?%[\w.\-]+ = (\S+) ([\w\-]+)\(", line)
        if not m or m.group(1).startswith("(") or not moved.search(
                m.group(1)):
            continue
        op = m.group(2)
        if op == "fusion":
            op = roots[re.search(r"calls=%([\w.\-]+)", line).group(1)]
        assert op in IN_PLACE, f"the pool is moved by: {line.strip()[:200]}"
    # in the module the chip runs, the loops whose carry is a counter and
    # a pool leaf (the layer scan carries the hidden state, and has the
    # pool beside it) stop at a number they are handed, not at 32
    appends = [line for line in hlo.splitlines()
               if re.search(r" while\(", line) and re.search(
                   r"= \(s32\[\]\S*, \S*" + moved.pattern, line)]
    assert len(appends) == len(kv)
    for line in appends:
        cond = comps[re.search(r"condition=%([\w.\-]+)", line).group(1)]
        assert not any(" constant(" in x for x in cond), cond
        compare, = [x for x in cond if " compare(" in x]
        assert compare.lstrip().startswith("ROOT") and "direction=LT" in compare


@pytest.fixture
def fresh_traces():
    """The same model at the same shapes is traced in this file as the
    CPU traces it (``ragged_dot``): that cached trace is not the test's,
    nor the test's a later one's."""
    jax.clear_caches()
    yield
    jax.clear_caches()


def test_olmoe_decode_program_is_one_grouped_call_a_matmul(
        one_chip, monkeypatch, fresh_traces):
    """The OLMoE decode program as a TPU traces it (``grouped_matmul``
    takes its Pallas kernel there), compiled by Mosaic at the cell's
    widths: a layer's three expert matmuls are three calls named
    ``ragged-dot-grouped`` — the family the benchmark's ``%ragged-dot``
    patterns read — with XLA's own ``ragged_dot`` kernel gone, and the
    ``[L·E, ...]`` stack goes to them whole: the program's scratch stays
    where the ``ragged_dot`` program's was, nowhere near a layer's slice
    of the stack (3 x 268 MB)."""
    from deepspeed_tpu.moe import sharded_moe
    from deepspeed_tpu.ops.pallas import tuning
    for module in ("paged_attention", "grouped_matmul"):
        monkeypatch.setattr(importlib.import_module(
            f"deepspeed_tpu.ops.pallas.{module}"), "_interpret",
            lambda: False)
    monkeypatch.setattr(sharded_moe, "on_tpu", lambda: True)
    model = OLMoE(OLMoEConfig(num_hidden_layers=LAYERS, dtype=jnp.bfloat16,
                              param_dtype=jnp.bfloat16, **OLMOE_WIDTH))
    args, static, _ = _decode_operands(model, one_chip)
    compiled = _compile_decode(model, args, static)
    record = tuning.last_dispatch("grouped_matmul")[
        f"groups{LAYERS * OLMOE_WIDTH['num_experts']}"]
    assert record["impl"] == "kernel"
    assert record["block_m"] == 128 and record["block_n"] in (1024, 2048)

    program = jax.make_jaxpr(lambda *a: _paged_decode_iter_impl(
        model, *a, *static))(*args).jaxpr
    calls = [eqn for eqn, _ in _equations(((program, None),), "pallas_call")]
    names = sorted(eqn.params["name"] or "" for eqn in calls)
    # under the layer scan: the paged kernel (named after its function)
    # and a layer's three matmuls
    assert names == [""] + ["ragged-dot-grouped"] * 3, names
    assert not list(_equations(((program, None),), "ragged_dot_general"))
    grouped = [eqn for eqn in calls if eqn.params["name"]]
    stack = LAYERS * OLMOE_WIDTH["num_experts"]
    for eqn in grouped:
        rows, weights = eqn.invars[-2:]
        assert rows.aval.shape[0] == SLOTS * OLMOE_WIDTH["num_experts_per_tok"]
        assert weights.aval.shape[0] == stack          # whole, not a slice

    hlo = compiled.as_text()
    lines = [x for x in hlo.splitlines() if "tpu_custom_call" in x]
    matmuls = [x for x in lines if re.match(
        r"\s*(?:ROOT )?%ragged-dot-grouped[^ ]* = ", x)]
    assert len(matmuls) == 3
    assert "%ragged-dot-none" not in hlo
    mem = compiled.memory_analysis()
    # the ``ragged_dot`` program's scratch was 4,657,664 bytes; the
    # walk's metadata is vectors of a number a visit
    assert mem.temp_size_in_bytes <= 4657664 + SMALL_VECTORS, \
        mem.temp_size_in_bytes


# the LFM2 cell (configs/lfm2-24b-a2b-10l-serve.json) at its published
# widths, its first four layers: conv, conv (dense), attention, conv
# (experts) — 32 query heads on 8 K/V heads of 64, 64 experts of 1536
LFM2_LAYERS = ("conv", "conv", "full_attention", "conv")
LFM2_PAGES, LFM2_MAX_PAGES = 1025, 32


@pytest.mark.parametrize("dtype,head_block,rows", [
    (jnp.float32, 2, 8), (jnp.bfloat16, 8, 32)], ids=["float32", "bfloat16"])
def test_lfm2_decode_program_keeps_pages_and_state_where_they_are(
        one_chip, monkeypatch, dtype, head_block, rows):
    """A model with recurrent state beside its pages: the decode program
    compiled for the chip holds the grouped-head Mosaic kernel — with
    float32 activations, as the cell serves it, a grid step of 2 K/V
    heads and their 8 query heads (the float32 arm's one-row cut of its
    boolean mask aborts this Mosaic past the eighth row); with a bf16
    pool all 8 K/V heads and 32 rows — moves neither the K/V pool nor
    the state stored a page, rewrites only the slots' state, and its
    scratch stays rows of activations with the convolution state in the
    program."""
    from deepspeed_tpu.inference.cache import has_recurrent_state
    from deepspeed_tpu.models.lfm2 import LFM2, LFM2Config
    from deepspeed_tpu.ops.pallas import tuning
    monkeypatch.setattr(
        importlib.import_module("deepspeed_tpu.ops.pallas.paged_attention"),
        "_interpret", lambda: False)
    model = LFM2(LFM2Config(num_hidden_layers=len(LFM2_LAYERS),
                            layer_types=LFM2_LAYERS,
                            max_position_embeddings=4096,
                            dtype=dtype, param_dtype=jnp.bfloat16))
    import flax.core.meta as flax_meta
    params = jax.eval_shape(
        lambda r: flax_meta.unbox(model.init(
            r, jnp.ones((1, 8), jnp.int32)))["params"],
        jax.random.PRNGKey(0))

    def pool():
        return init_page_pool(model, params, LFM2_PAGES, PAGE_LEN, SLOTS)

    def on_chip(tree):
        shapes = jax.eval_shape(tree) if callable(tree) else tree
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one_chip), shapes)

    slot = lambda dtype: jax.ShapeDtypeStruct((SLOTS,), dtype)
    state = {"lengths": slot(jnp.int32), "last_token": slot(jnp.int32),
             "active": slot(jnp.bool_), "remaining": slot(jnp.int32)}
    pool_shapes = on_chip(pool)
    assert has_recurrent_state(pool_shapes)
    unit = pool_shapes["layers_0"]["conv"]
    assert unit["conv_state"].shape == (SLOTS, 2, 2048)
    assert unit["page_state"].shape == (LFM2_PAGES, 2, 2048)
    assert pool_shapes["layers_2"]["attn"]["cached_key"].shape \
        == (LFM2_PAGES, 8, 64, PAGE_LEN)           # the K/V heads, not 32
    args = (on_chip(params), pool_shapes,
            on_chip(jax.ShapeDtypeStruct((SLOTS, LFM2_MAX_PAGES), jnp.int32)),
            on_chip(state), on_chip(lambda: jax.random.PRNGKey(0)),
            on_chip(jax.ShapeDtypeStruct((), jnp.int32)))
    static = (65535, 1.0, 0, 1.0, None, True, False, False, True, dtype)
    tuning.clear_last_dispatch()
    compiled = jax.jit(
        _paged_decode_iter_impl, static_argnums=(0, 11, 12, 13, 14, 15, 16),
        donate_argnums=(2, 4)).lower(model, *args, *static).compile()
    rec = tuning.last_dispatch("paged_attention")["page%d" % PAGE_LEN]
    assert (rec["impl"], rec["head_block"], rec["rows"]) == (
        "kernel", head_block, rows)
    assert rec["products"] == jnp.dtype(dtype).name

    leaves = jax.tree.leaves(pool_shapes)
    pool_bytes = sum(x.size * x.dtype.itemsize for x in leaves)
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= pool_bytes
    # rows of activations (in float32, 32 rows of 65,536 logits are
    # 8.4 MB): nowhere near a K/V leaf (134 MB in bf16), a layer's page
    # states (16.8 MB in bf16) or one expert's weights (18.9 MB)
    wide = jnp.dtype(dtype).itemsize // 2
    assert mem.temp_size_in_bytes < wide * 8 * 2 ** 20, mem.temp_size_in_bytes
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo and hlo_has(compiled, "ragged-dot")
    roots = _roots(_computations(hlo))
    moved = re.compile(r"\[%d,(?:8,64,%d|2,2048)\]" % (LFM2_PAGES, PAGE_LEN))
    for line in hlo.splitlines():
        m = re.match(r"\s*(?:ROOT )?%[\w.\-]+ = (\S+) ([\w\-]+)\(", line)
        if not m or m.group(1).startswith("(") or not moved.search(
                m.group(1)):
            continue
        op = m.group(2)
        if op == "fusion":
            op = roots[re.search(r"calls=%([\w.\-]+)", line).group(1)]
        assert op in IN_PLACE, f"moved by: {line.strip()[:200]}"


# the Falcon-H1 cell (configs/falcon-h1-34b-9l-serve.json) at its
# published widths, two layers deep, its slice of the vocabulary: a Mamba-2
# mixer of 32 heads x 256 x 128 (4 MB of float32 state a slot a layer)
# beside 20 query heads on 4 K/V heads of 128; 64 slots of 2048 positions
H1_SLOTS, H1_PAGES, H1_MAX_PAGES, H1_SNAPSHOTS = 64, 1025, 16, 32


def test_falcon_h1_decode_program_updates_the_slot_state_in_place(
        one_chip, monkeypatch):
    """Two kinds of state beside each other: the decode program compiled
    for the chip holds both Mosaic kernels (paged attention over 4 K/V
    heads of 128, and the mixer's state update), aliases the whole pool —
    pages, every slot's matrix state (268 MB a layer) and the snapshot
    pool — and moves none of it: a copy of one layer's slot state would
    be 268 MB of scratch, where the program takes rows of activations."""
    from deepspeed_tpu.inference.cache import has_snapshot_pool
    from deepspeed_tpu.models.falcon_h1 import FalconH1, FalconH1Config
    from deepspeed_tpu.ops.pallas import tuning
    monkeypatch.setattr(
        importlib.import_module("deepspeed_tpu.ops.pallas.paged_attention"),
        "_interpret", lambda: False)
    monkeypatch.setattr(
        importlib.import_module("deepspeed_tpu.ops.pallas.ssm_update"),
        "on_tpu", lambda: True)
    model = FalconH1(FalconH1Config(
        num_hidden_layers=2, vocab_size=32640, max_position_embeddings=2048,
        dtype=jnp.bfloat16, param_dtype=jnp.bfloat16))
    import flax.core.meta as flax_meta
    params = jax.eval_shape(
        lambda r: flax_meta.unbox(model.init(
            r, jnp.ones((1, 8), jnp.int32)))["params"],
        jax.random.PRNGKey(0))

    def on_chip(tree):
        shapes = jax.eval_shape(tree) if callable(tree) else tree
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one_chip), shapes)

    slot = lambda dtype: jax.ShapeDtypeStruct((H1_SLOTS,), dtype)
    state = {"lengths": slot(jnp.int32), "last_token": slot(jnp.int32),
             "active": slot(jnp.bool_), "remaining": slot(jnp.int32)}
    pool_shapes = on_chip(lambda: init_page_pool(
        model, params, H1_PAGES, PAGE_LEN, H1_SLOTS, H1_SNAPSHOTS))
    assert has_snapshot_pool(pool_shapes)
    unit = pool_shapes["layers_0"]["mixer"]
    assert unit["ssm_state"].shape == (H1_SLOTS, 32, 256, 128)
    assert unit["conv_state"].shape == (H1_SLOTS, 3, 5120)
    assert unit["snapshots"]["ssm_state"].shape \
        == (H1_SNAPSHOTS + 1, 32, 256, 128)      # and the null entry
    assert pool_shapes["layers_1"]["attn"]["cached_key"].shape \
        == (H1_PAGES, 4, 128, PAGE_LEN)
    args = (on_chip(params), pool_shapes,
            on_chip(jax.ShapeDtypeStruct((H1_SLOTS, H1_MAX_PAGES),
                                         jnp.int32)),
            on_chip(state), on_chip(lambda: jax.random.PRNGKey(0)),
            on_chip(jax.ShapeDtypeStruct((), jnp.int32)))
    static = (32639, 1.0, 0, 1.0, None, True, False, False, True,
              jnp.bfloat16)
    tuning.clear_last_dispatch()
    compiled = jax.jit(
        _paged_decode_iter_impl, static_argnums=(0, 11, 12, 13, 14, 15, 16),
        donate_argnums=(2, 4)).lower(model, *args, *static).compile()
    rec = tuning.last_dispatch("paged_attention")["page%d" % PAGE_LEN]
    # every K/V head and its five query heads in one grid step, at the
    # block swept on the chip (PR 49): the grid is the 64 rows
    assert (rec["impl"], rec["head_block"], rec["rows"]) == ("kernel", 4, 20)
    assert (rec["source"], rec["block_k"]) == ("defaults", 512)
    rec, = tuning.last_dispatch("ssm_update").values()
    assert rec["impl"] == "kernel"

    pool_bytes = sum(x.size * x.dtype.itemsize
                     for x in jax.tree.leaves(pool_shapes))
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= pool_bytes
    # rows of activations (64 x 21504 float32 is 5.5 MB): nowhere near a
    # layer's slot state (268 MB) or one of its snapshots (4 MB)
    assert mem.temp_size_in_bytes < 48 * 2 ** 20, mem.temp_size_in_bytes
    hlo = compiled.as_text()
    assert hlo.count("%ssm_update") >= 2 and "tpu_custom_call" in hlo
    roots = _roots(_computations(hlo))
    moved = re.compile(r"\[(?:%d|%d),32,256,128\]|\[%d,4,128,%d\]" % (
        H1_SLOTS, H1_SNAPSHOTS + 1, H1_PAGES, PAGE_LEN))
    for line in hlo.splitlines():
        m = re.match(r"\s*(?:ROOT )?%[\w.\-]+ = (\S+) ([\w\-]+)\(", line)
        if not m or m.group(1).startswith("(") or not moved.search(
                m.group(1)):
            continue
        op = m.group(2)
        if op == "fusion":
            op = roots[re.search(r"calls=%([\w.\-]+)", line).group(1)]
        assert op in IN_PLACE, f"moved by: {line.strip()[:200]}"


# the Kanana-2 cell (configs/kanana-2-30b-a3b-serve.json) at its
# published widths, its first two layers: the dense one and one of
# experts — 32 heads on a latent 512 + 64 wide, 128 experts of 768 and
# the shared MLP of 1536; slots of 9216 positions in 72 pages
KANANA_PAGES, KANANA_MAX_PAGES = 1025, 72


def _kanana(dtype):
    from deepspeed_tpu.models.deepseek_v3 import DeepseekV3, DeepseekV3Config
    import flax.core.meta as flax_meta
    model = DeepseekV3(DeepseekV3Config(
        num_hidden_layers=2, max_position_embeddings=9216, dtype=dtype,
        param_dtype=jnp.bfloat16))
    params = jax.eval_shape(
        lambda r: flax_meta.unbox(model.init(
            r, jnp.ones((1, 8), jnp.int32)))["params"],
        jax.random.PRNGKey(0))
    return model, params


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_kanana_decode_program_walks_the_latent_pool_in_place(
        one_chip, monkeypatch, dtype):
    """A latent pool: ONE leaf a layer, ``[pages, 1, 576, page_len]``,
    no values beside it. The decode program compiled for the chip holds
    the latent Mosaic kernel (all 32 query heads a grid step, no head's
    rows cut out of the query), aliases the whole pool and moves none of
    it, and its scratch stays rows of activations."""
    from deepspeed_tpu.inference.cache import has_latent_units
    from deepspeed_tpu.ops.pallas import tuning
    monkeypatch.setattr(
        importlib.import_module("deepspeed_tpu.ops.pallas.latent_attention"),
        "_interpret", lambda: False)
    model, params = _kanana(dtype)

    def on_chip(tree):
        shapes = jax.eval_shape(tree) if callable(tree) else tree
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one_chip), shapes)

    pool_shapes = on_chip(
        lambda: init_page_pool(model, params, KANANA_PAGES, PAGE_LEN))
    assert has_latent_units(pool_shapes)
    unit = pool_shapes["layers_1"]["attn"]
    assert set(unit) == {"cached_key", "cache_index"}
    assert unit["cached_key"].shape == (KANANA_PAGES, 1, 576, PAGE_LEN)
    assert unit["cached_key"].dtype == dtype
    slot = lambda kind: jax.ShapeDtypeStruct((SLOTS,), kind)
    state = {"lengths": slot(jnp.int32), "last_token": slot(jnp.int32),
             "active": slot(jnp.bool_), "remaining": slot(jnp.int32)}
    args = (on_chip(params), pool_shapes,
            on_chip(jax.ShapeDtypeStruct((SLOTS, KANANA_MAX_PAGES),
                                         jnp.int32)),
            on_chip(state), on_chip(lambda: jax.random.PRNGKey(0)),
            on_chip(jax.ShapeDtypeStruct((), jnp.int32)))
    static = (128255, 1.0, 0, 1.0, None, True, False, False, True, dtype)
    tuning.clear_last_dispatch()
    compiled = jax.jit(
        _paged_decode_iter_impl, static_argnums=(0, 11, 12, 13, 14, 15, 16),
        donate_argnums=(2, 4)).lower(model, *args, *static).compile()
    rec = tuning.last_dispatch("latent_attention")["page%d" % PAGE_LEN]
    assert (rec["impl"], rec["block_k"]) == ("kernel", 512)
    assert not tuning.last_dispatch("paged_attention")

    pool_bytes = sum(x.size * x.dtype.itemsize
                     for x in jax.tree.leaves(pool_shapes))
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= pool_bytes
    # rows of activations (32 rows of 128,256 float32 logits are 16 MB):
    # nowhere near a layer's pool leaf (151 MB in bf16)
    assert mem.temp_size_in_bytes < 32 * 2 ** 20, mem.temp_size_in_bytes
    hlo = compiled.as_text()
    assert "%latent_attn" in hlo and hlo_has(compiled, "ragged-dot")
    roots = _roots(_computations(hlo))
    moved = re.compile(r"\[%d,1,576,%d\]" % (KANANA_PAGES, PAGE_LEN))
    for line in hlo.splitlines():
        m = re.match(r"\s*(?:ROOT )?%[\w.\-]+ = (\S+) ([\w\-]+)\(", line)
        if not m or m.group(1).startswith("(") or not moved.search(
                m.group(1)):
            continue
        op = m.group(2)
        if op == "fusion":
            op = roots[re.search(r"calls=%([\w.\-]+)", line).group(1)]
        assert op in IN_PLACE, f"moved by: {line.strip()[:200]}"


# ---------------------------------------------------------------------------
# the training cells' flash calls (PR 45): Mosaic takes them at the cells'
# shapes with the committed table's blocks — an unrolled program keeps a
# float32 tile of every step in VMEM, and 16 MB is the scoped limit
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape", [
    (32, 12, 1024, 64),      # train-125m-zero1
    (4, 16, 2048, 128),      # train-1p3b-zero3-4chip, a chip's share
    (8, 16, 2048, 128),      # the same at micro 8 (ROADMAP 1.0c)
    (1, 8, 4096, 128),       # too many scores to unroll: fori_loops
], ids=lambda x: "x".join(map(str, x)))
def test_flash_training_calls_compile_for_the_chip(one_chip, monkeypatch,
                                                   fresh_traces, shape):
    from deepspeed_tpu.ops.pallas import tuning
    fa = importlib.import_module("deepspeed_tpu.ops.pallas.flash_attention")
    monkeypatch.setattr(fa, "_interpret", lambda: False)
    b, h, s, d = shape
    x = jax.ShapeDtypeStruct((b, s, h, d), jnp.bfloat16, sharding=one_chip)
    tuning.clear_last_dispatch()
    compiled = jax.jit(jax.grad(
        lambda q, k, v: fa.flash_attention(q, k, v, causal=True)
        .astype(jnp.float32).sum(), argnums=(0, 1, 2))).lower(
            x, x, x).compile()
    rec = tuning.last_dispatch()
    assert set(rec) == {"fwd_resident", "bwd_monolithic"}
    for r in rec.values():
        assert r["source"] == "defaults"
        assert r["tiles_visited"] < r["tiles_total"], r
        assert fa._unrolled(s, s, r["block_q"], r["block_k"], True) == (
            s < 4096)
    # one forward and one backward Mosaic call; the backward's result
    # holds three bf16[B,H,S,D] and no other 4-D bf16 array is a
    # Mosaic call's result (what the benchmark's trace reader counts)
    calls = [l for l in compiled.as_text().splitlines()
             if 'custom_call_target="tpu_custom_call"' in l]
    bhsd = "bf16[%d,%d,%d,%d]" % (b, h, s, d)
    results = sorted(l.split(" custom-call(")[0].count(bhsd) for l in calls)
    assert results == [1, 3], results


def test_phi4flash_decode_program_keeps_pages_rings_and_states_in_place(
        one_chip, monkeypatch):
    """Three kinds of state in one pool: the decode program of the first
    eight layers' kinds at published widths (mixers, two window layers,
    the full layer, a memory unit, a cross layer) compiled for the chip
    holds the paged kernel twice — the full layer's call and the cross
    layer's, over the same pages — and, once a window layer, the ring's
    write (``%ring_append``, both rings aliased through it) and the
    contiguous decode kernel that reads them; it aliases the whole pool
    and moves none of it: a copy of one layer's rings would be 84 MB of
    scratch, of its pages 335 MB."""
    from deepspeed_tpu.inference.cache import has_ring_units, state_bytes
    from deepspeed_tpu.models.phi4flash import Phi4Flash, Phi4FlashConfig
    from deepspeed_tpu.ops.pallas import tuning
    for name in ("paged_attention", "decode_attention", "ring_append"):
        monkeypatch.setattr(
            importlib.import_module(f"deepspeed_tpu.ops.pallas.{name}"),
            "_interpret", lambda: False)
    slots, pages, max_pages = 64, 2049, 32
    model = Phi4Flash(Phi4FlashConfig(
        num_hidden_layers=8, vocab_size=25008, max_position_embeddings=4096,
        dtype=jnp.bfloat16, param_dtype=jnp.bfloat16))
    import flax.core.meta as flax_meta
    params = jax.eval_shape(
        lambda r: flax_meta.unbox(model.init(
            r, jnp.ones((1, 8), jnp.int32)))["params"],
        jax.random.PRNGKey(0))

    def on_chip(tree):
        shapes = jax.eval_shape(tree) if callable(tree) else tree
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one_chip), shapes)

    slot = lambda dtype: jax.ShapeDtypeStruct((slots,), dtype)
    state = {"lengths": slot(jnp.int32), "last_token": slot(jnp.int32),
             "active": slot(jnp.bool_), "remaining": slot(jnp.int32)}
    pool_shapes = on_chip(lambda: init_page_pool(
        model, params, pages, PAGE_LEN, slots, 32))
    assert has_ring_units(pool_shapes)
    assert sorted(pool_shapes) == [f"layers_{i}" for i in range(6)]
    assert pool_shapes["layers_5"]["shared_attn"]["cached_key"].shape \
        == (pages, 10, 128, PAGE_LEN)
    assert pool_shapes["layers_1"]["window_attn"]["ring_key"].shape \
        == (slots, 10, 128, 512)
    mixer = pool_shapes["layers_4"]["mixer"]
    assert set(mixer) == {"conv_state", "ssm_state"}   # no page-end states
    assert mixer["ssm_state"].shape == (slots, 16, 5120)
    assert state_bytes(pool_shapes) == slots * (
        2 * 2 * 10 * 128 * 512 * 2 + 3 * (16 + 3) * 5120 * 4)
    args = (on_chip(params), pool_shapes,
            on_chip(jax.ShapeDtypeStruct((slots, max_pages), jnp.int32)),
            on_chip(state), on_chip(lambda: jax.random.PRNGKey(0)),
            on_chip(jax.ShapeDtypeStruct((), jnp.int32)))
    static = (25007, 1.0, 0, 1.0, None, True, False, False, True,
              jnp.bfloat16)
    tuning.clear_last_dispatch()
    compiled = _compile_decode(model, args, static)
    rec = tuning.last_dispatch("paged_attention")["page%d" % PAGE_LEN]
    assert (rec["impl"], rec["head_block"], rec["rows"], rec["products"],
            rec["block_k"], rec["source"]) \
        == ("kernel", 10, 40, "bfloat16", 128, "defaults")
    ring, = tuning.last_dispatch("decode_attention").values()
    assert (ring["impl"], ring["key"], ring["head_block"], ring["rows"],
            ring["block_k"], ring["source"]) \
        == ("kernel", "decode_attention/dma/sq64_sk512_d128_bfloat16_causal",
            10, 40, 128, "defaults")
    write, = tuning.last_dispatch("ring_append").values()
    assert (write["impl"], write["key"], write["tile"]) \
        == ("kernel", "b64_h10_d128_w512_bfloat16", 128)

    hlo = compiled.as_text()
    calls = [line.split(" = ")[0].strip() for line in hlo.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert sorted(re.sub(r"\.\d+$", "", c) for c in calls) == [
        "%cross_attn", "%ring_append", "%ring_append", "%shared_attn",
        "%window_attn", "%window_attn"]
    pool_bytes = sum(x.size * x.dtype.itemsize
                     for x in jax.tree.leaves(pool_shapes))
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= pool_bytes
    # rows of activations and logits (64 x 25,008 float32 is 6.4 MB)
    assert mem.temp_size_in_bytes < 48 * 2 ** 20, mem.temp_size_in_bytes
    roots = _roots(_computations(hlo))
    moved = re.compile(r"\[%d,10,128,(?:%d|512)\]|\[%d,16,5120\]" % (
        pages, PAGE_LEN, slots) + r"|\[%d,10,128,512\]" % slots)
    for line in hlo.splitlines():
        m = re.match(r"\s*(?:ROOT )?%[\w.\-]+ = (\S+) ([\w\-]+)\(", line)
        if not m or m.group(1).startswith("(") or not moved.search(
                m.group(1)):
            continue
        op = m.group(2)
        if op == "fusion":
            op = roots[re.search(r"calls=%([\w.\-]+)", line).group(1)]
        # the mixer's update rewrites every slot's state: a fusion whose
        # root is the new state, in the buffer of the old
        assert op in IN_PLACE or "16,5120" in m.group(1), \
            f"moved by: {line.strip()[:200]}"
