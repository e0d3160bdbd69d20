"""Shape-keyed kernel tuning cache + sweep harness (CPU-mesh tests).

The acceptance contract: the flash-attention dispatch reads block sizes
from the tuning cache with a committed default table, and the
hit / miss-to-defaults / fallback-to-constants paths are all proven
here (interpret-mode kernels — no hardware needed; only the timing
NUMBERS need a real chip).
"""

import json
import os
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import importlib

from deepspeed_tpu.ops.pallas import flash_attention, tuning

# the package re-exports the flash_attention FUNCTION over the module
# name; importlib reaches the module itself (for monkeypatching gates)
fa_mod = importlib.import_module("deepspeed_tpu.ops.pallas.flash_attention")
from deepspeed_tpu.ops.transformer.attention import _reference_attention

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))


@pytest.fixture(autouse=True)
def _clean_tables():
    tuning.set_tuning_table(None)
    tuning.clear_last_dispatch()
    yield
    tuning.set_tuning_table(None)
    tuning.clear_last_dispatch()


def _qkv(s, d=64, b=1, h=2, dtype=jnp.float32):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
    return (jax.random.normal(k1, (b, s, h, d), dtype),
            jax.random.normal(k2, (b, s, h, d), dtype),
            jax.random.normal(k3, (b, s, h, d), dtype))


class TestCacheLayers:
    def test_runtime_table_hit_drives_dispatch(self):
        q, k, v = _qkv(256)
        key = tuning.make_key("flash_attention", "fwd_resident",
                              sq=256, sk=256, d=64, dtype=q.dtype,
                              causal=True)
        with tuning.tuning_table({key: {"block_q": 128, "block_k": 128}}):
            out = flash_attention(q, k, v, causal=True)
        disp = tuning.last_dispatch()["fwd_resident"]
        assert disp["source"] == "runtime"
        assert disp["block_q"] == 128 and disp["block_k"] == 128
        # and the tuned tiling computes the right thing
        ref = _reference_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=5e-3, atol=5e-3)

    def test_miss_falls_back_to_committed_defaults(self):
        # bf16 s1024 d128 causal is a committed (hand-seeded) entry
        entry, key, source = tuning.lookup(
            "flash_attention", "fwd_resident", sq=1024, sk=1024, d=128,
            dtype=jnp.bfloat16, causal=True)
        assert source == "defaults"
        assert entry["block_q"] == 512 and entry["block_k"] == 512

    def test_full_miss_falls_back_to_constants(self):
        q, k, v = _qkv(256)  # fp32 s256: in no table
        flash_attention(q, k, v, causal=True)
        disp = tuning.last_dispatch()["fwd_resident"]
        assert disp["source"] == "constants"
        # the constants, validated down to the shape's divisors
        assert disp["block_q"] == 256 and disp["block_k"] == 256

    def test_env_artifact_layer(self, tmp_path, monkeypatch):
        q, k, v = _qkv(256)
        key = tuning.make_key("flash_attention", "fwd_resident",
                              sq=256, sk=256, d=64, dtype=q.dtype,
                              causal=True)
        path = tmp_path / "tuned.json"
        tuning.save_artifact(str(path), {key: {"block_q": 128,
                                               "block_k": 256}},
                             device="test")
        monkeypatch.setenv(tuning.ENV_VAR, str(path))
        flash_attention(q, k, v, causal=True)
        disp = tuning.last_dispatch()["fwd_resident"]
        assert disp["source"] == "env" and disp["block_q"] == 128

    def test_explicit_block_q_overrides_cache(self):
        q, k, v = _qkv(256)
        key = tuning.make_key("flash_attention", "fwd_resident",
                              sq=256, sk=256, d=64, dtype=q.dtype,
                              causal=True)
        with tuning.tuning_table({key: {"block_q": 256, "block_k": 256}}):
            flash_attention(q, k, v, causal=True, block_q=128)
        disp = tuning.last_dispatch()["fwd_resident"]
        assert disp["source"] == "caller" and disp["block_q"] == 128

    def test_illegal_cache_entry_is_sanitized(self):
        # a stale/foreign entry (block sizes that don't divide the shape)
        # must be clamped to a legal tiling, never crash the kernel
        q, k, v = _qkv(256)
        key = tuning.make_key("flash_attention", "fwd_resident",
                              sq=256, sk=256, d=64, dtype=q.dtype,
                              causal=True)
        with tuning.tuning_table({key: {"block_q": 192, "block_k": 7000}}):
            out = flash_attention(q, k, v, causal=True)
        disp = tuning.last_dispatch()["fwd_resident"]
        assert 256 % disp["block_q"] == 0 and 256 % disp["block_k"] == 0
        ref = _reference_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=5e-3, atol=5e-3)

    def test_defaults_file_is_valid_artifact(self):
        art = tuning.load_artifact(tuning.DEFAULTS_PATH)
        assert art["entries"], "committed default table must not be empty"
        for key, e in art["entries"].items():
            # a flash entry tiles the queries; a paged-decode entry (one
            # query token a row) the pooled tokens of a DMA block, as a
            # contiguous cache's does; a grouped-matmul entry the rows of
            # a visit
            kernel = key.split("/")[0]
            block = {"flash_attention": "block_q",
                     "paged_attention": "block_k",
                     "decode_attention": "block_k",
                     "grouped_matmul": "block_m"}[kernel]
            assert isinstance(e.get(block), int), (key, e)


class TestBwdStructures:
    def test_bwd_monolithic_consults_cache(self):
        q, k, v = _qkv(256)
        key = tuning.make_key("flash_attention", "bwd_monolithic",
                              sq=256, sk=256, d=64, dtype=q.dtype,
                              causal=True)

        def loss(q, k, v):
            return flash_attention(q, k, v, causal=True).sum()

        g0 = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        with tuning.tuning_table({key: {"block_q": 128}}):
            g1 = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        disp = tuning.last_dispatch()["bwd_monolithic"]
        assert disp["source"] == "runtime" and disp["block_q"] == 128
        for a, b in zip(g0, g1):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-4)

    def test_bwd_monolithic_key_carries_block_k(self):
        """The one-pass backward's inner loop walks k blocks, so its
        table entry tiles both sides; an entry without ``block_k`` (an
        artifact of before PR 45) falls back to the resident constant."""
        q, k, v = _qkv(512)
        key = tuning.make_key("flash_attention", "bwd_monolithic",
                              sq=512, sk=512, d=64, dtype=q.dtype,
                              causal=True)
        grad = jax.grad(lambda q, k, v: flash_attention(
            q, k, v, causal=True).sum(), argnums=(0, 1, 2))
        with tuning.tuning_table({key: {"block_q": 256, "block_k": 128}}):
            grad(q, k, v)
        disp = tuning.last_dispatch()["bwd_monolithic"]
        assert disp["source"] == "runtime"
        assert (disp["block_q"], disp["block_k"]) == (256, 128)
        with tuning.tuning_table({key: {"block_q": 256}}):
            grad(q, k, v)
        disp = tuning.last_dispatch()["bwd_monolithic"]
        assert (disp["block_q"], disp["block_k"]) == (
            256, fa_mod.RESIDENT_BLOCK_K)

    @pytest.mark.parametrize("sq,sk,bq,bk,causal", [
        (512, 512, 128, 128, True), (512, 512, 256, 128, True),
        (512, 512, 128, 256, True), (512, 512, 512, 512, True),
        (256, 512, 128, 128, True), (256, 512, 128, 256, True),
        (512, 512, 128, 256, False)],
        ids=lambda x: str(x))
    def test_dispatch_records_the_tiles_it_visits(self, sq, sk, bq, bk,
                                                  causal):
        """``tiles_visited`` / ``tiles_total`` of one (batch, head)
        program, forward and backward, equal a count made here from the
        shape: a q block's k tiles end at its last row's last visible
        key (``causal_shift`` = sk - sq)."""
        k1, k2, k3 = jax.random.split(jax.random.PRNGKey(1), 3)
        q = jax.random.normal(k1, (1, sq, 1, 64), jnp.float32)
        k = jax.random.normal(k2, (1, sk, 1, 64), jnp.float32)
        v = jax.random.normal(k3, (1, sk, 1, 64), jnp.float32)
        kw = dict(sq=sq, sk=sk, d=64, dtype=q.dtype, causal=causal)
        table = {tuning.make_key("flash_attention", s, **kw):
                 {"block_q": bq, "block_k": bk}
                 for s in ("fwd_resident", "bwd_monolithic")}
        with tuning.tuning_table(table):
            jax.grad(lambda q: flash_attention(q, k, v, causal=causal)
                     .sum())(q)
        visited = 0
        for i in range(sq // bq):
            last_key = (i + 1) * bq - 1 + (sk - sq) if causal else sk - 1
            visited += sum(1 for j in range(sk // bk) if j * bk <= last_key)
        total = (sq // bq) * (sk // bk)
        for structure in ("fwd_resident", "bwd_monolithic"):
            disp = tuning.last_dispatch()[structure]
            assert (disp["block_q"], disp["block_k"]) == (bq, bk)
            assert (disp["tiles_visited"], disp["tiles_total"]) == (
                visited, total), (structure, disp)

    @pytest.mark.parametrize("shape", ["sq1024_sk1024_d64",
                                       "sq2048_sk2048_d128"])
    @pytest.mark.parametrize("structure", ["fwd_resident",
                                           "bwd_monolithic"])
    def test_training_cells_entries_are_measured(self, structure, shape):
        """The four entries the two training cells dispatch
        (``train-125m-zero1``: [32,12,1024,64]; ``train-1p3b-zero3-4chip``:
        [4,16,2048,128] a chip) were swept on the chip, kernel alone:
        a time, the device that gave it, and the whole row in a note."""
        art = tuning.load_artifact(tuning.DEFAULTS_PATH)
        e = art["entries"][
            f"flash_attention/{structure}/{shape}_bfloat16_causal"]
        assert isinstance(e["ms"], float) and e["ms"] > 0
        assert "hand-seeded" not in e["device"] and "v5" in e["device"]
        assert isinstance(e["block_q"], int) and isinstance(
            e["block_k"], int)
        assert "PR 45" in e["note"]

    def test_bwd_two_pass_consults_cache(self, monkeypatch):
        # force past the monolithic gate to reach the two-pass resident bwd
        monkeypatch.setattr(fa_mod, "MONOLITHIC_BWD_MAX_SEQ", 128)
        q, k, v = _qkv(256)
        key = tuning.make_key("flash_attention", "bwd_resident",
                              sq=256, sk=256, d=64, dtype=q.dtype,
                              causal=True)

        def loss(q, k, v):
            return flash_attention(q, k, v, causal=True).sum()

        with tuning.tuning_table({key: {"block_q": 128, "block_k": 128}}):
            jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        disp = tuning.last_dispatch()["bwd_resident"]
        assert disp["source"] == "runtime"
        assert disp["block_q"] == 128 and disp["block_k"] == 128


class TestSweepHarness:
    @pytest.mark.slow
    def test_sweep_writes_consumable_artifact(self, tmp_path):
        from benchmarks.kernel_tuning import sweep_flash_attention
        entries = sweep_flash_attention(
            1, 1, 128, 128, 64, dtype="float32", causal=True, trials=1,
            warmup=1, max_candidates=1, log=lambda *a: None)
        # the shape dispatches resident fwd + monolithic bwd
        assert any("fwd_resident" in k for k in entries)
        assert any("bwd_monolithic" in k for k in entries)
        for e in entries.values():
            assert e["ms"] > 0
        path = tmp_path / "sweep.json"
        art = tuning.save_artifact(str(path), entries, device="cpu-interpret")
        assert art["format"] == tuning.FORMAT
        # the dispatch consumes the artifact through the runtime layer
        tuning.set_tuning_table(str(path))
        q, k, v = _qkv(128, h=1)
        flash_attention(q, k, v, causal=True)
        assert tuning.last_dispatch()["fwd_resident"]["source"] == "runtime"

    def test_candidate_grid_respects_divisibility(self):
        from benchmarks.kernel_tuning import candidate_grid
        for bq, bk in candidate_grid("fwd_resident", 384, 384):
            assert 384 % bq == 0 and 384 % bk == 0
        # the one-pass backward tiles the keys too (PR 45): pairs
        assert candidate_grid("bwd_monolithic", 256, 256) == [
            (256, 256), (256, 128), (128, 256), (128, 128)]

    def test_paged_sweep_times_the_rows_that_decode(self):
        """``lengths`` names the rows that decode (the others are handed
        length 0, as the server hands them), the query goes in the
        pool's type, ``calls`` chains calls in one timed program, and
        the winner's entry keeps every candidate's time."""
        from benchmarks.kernel_tuning import sweep_paged_attention
        tuning.clear_last_dispatch()
        (key, entry), = sweep_paged_attention(
            4, 2, 16, 16, 4, dtype="bfloat16", lengths=[20, 33], calls=2,
            trials=1, max_candidates=2, log=lambda *a: None).items()
        assert key.startswith("paged_attention/page16/sq4_sk64_d16_bfloat16")
        assert len(entry["swept"]) == 2 and entry["ms"] == min(
            e["ms"] for e in entry["swept"])
        rec = tuning.last_dispatch("paged_attention")["page16"]
        assert rec["products"] == "bfloat16" and rec["impl"] == "kernel"
        # a row cannot be longer than its table
        with pytest.raises(ValueError, match="lengths"):
            sweep_paged_attention(4, 2, 16, 16, 4, lengths=[64], trials=1,
                                  log=lambda *a: None)
        with pytest.raises(ValueError, match="lengths"):
            sweep_paged_attention(2, 2, 16, 16, 4, lengths=[5, 5, 5],
                                  trials=1, log=lambda *a: None)

    @pytest.mark.parametrize("dtype,kv_int8,head_blocks", [
        ("bfloat16", False, [4, 2, 1]), ("float32", False, [1]),
        ("float32", True, [1])], ids=["bf16", "f32", "int8"])
    def test_paged_sweep_of_grouped_heads(self, dtype, kv_int8, head_blocks):
        """``kv_heads``: the pool is built with the K/V heads and the
        query heads are grouped on them (20 on 4, Falcon-H1's layout);
        the head blocks offered are the ones the dispatcher allows — up
        to every K/V head of a bf16 pool, eight rows' worth where the
        products are float32 — and every candidate names its rows."""
        from benchmarks.kernel_tuning import (_paged_candidates,
                                              sweep_paged_attention)
        pool = jnp.int8 if kv_int8 else jnp.dtype(dtype)
        assert _paged_candidates(4, 5, pool, 16, 2) == [
            (bk, hb) for bk in (16, 32) for hb in head_blocks]
        (key, entry), = sweep_paged_attention(
            3, 20, 16, 16, 2, dtype=dtype, kv_int8=kv_int8, kv_heads=4,
            lengths=[20, 0, 31], calls=2, trials=1,
            log=lambda *a: None).items()
        assert key.startswith("paged_attention/page16/sq3_sk32_d16_")
        swept = entry["swept"]
        assert [(e["block_k"], e["head_block"]) for e in swept] == [
            (bk, hb) for bk in (16, 32) for hb in head_blocks]
        assert all(e["rows"] == 5 * e["head_block"] for e in swept)
        assert entry["ms"] == min(e["ms"] for e in swept)
        # 51 valid columns of K and V, 4 heads of 16
        assert entry["bytes_us"] == round(
            2 * 51 * 4 * 16 * jnp.dtype(pool).itemsize / 819e9 * 1e6, 2)
        rec = tuning.last_dispatch("paged_attention")["page16"]
        assert rec["rows"] == 5 * rec["head_block"]
        with pytest.raises(ValueError, match="whole group"):
            sweep_paged_attention(3, 20, 16, 16, 2, kv_heads=3, trials=1,
                                  log=lambda *a: None)

    @pytest.mark.parametrize("dtype,kv_int8,head_blocks", [
        ("bfloat16", False, [16, 8, 4, 2, 1]),
        ("float32", False, [8, 4, 2, 1]), ("float32", True, [8, 4, 2, 1])],
        ids=["bf16", "f32", "int8"])
    def test_paged_sweep_of_sixteen_heads(self, dtype, kv_int8, head_blocks):
        """Sixteen ungrouped heads, the three bf16 serving cells' layout:
        the sweep offers a bf16 pool every head in one grid step beside
        8, 4, 2 and 1, a float32 or int8 pool eight at most, and the
        dispatch ran each candidate at the head block it names."""
        from benchmarks.kernel_tuning import sweep_paged_attention
        ran = []
        (key, entry), = sweep_paged_attention(
            3, 16, 16, 16, 2, dtype=dtype, kv_int8=kv_int8,
            lengths=[20, 0, 31], calls=2, trials=1,
            log=lambda line: ran.append(line)).items()
        assert key.startswith("paged_attention/page16/sq3_sk32_d16_")
        swept = entry["swept"]
        assert [(e["block_k"], e["head_block"]) for e in swept] == [
            (bk, hb) for bk in (16, 32) for hb in head_blocks]
        assert all(e["rows"] == e["head_block"] for e in swept)
        assert not any("infeasible" in line for line in ran)
        # the last candidate's dispatch: one head a step
        rec = tuning.last_dispatch("paged_attention")["page16"]
        assert (rec["head_block"], rec["rows"]) == (1, 1)

    @pytest.mark.parametrize("dtype,head_blocks", [
        ("bfloat16", [10, 5, 2, 1]), ("float32", [2, 1]), ("int8", [2, 1])])
    def test_sweeps_offer_ten_cached_heads_five_and_ten(self, dtype,
                                                        head_blocks):
        """Ten K/V heads under four query heads each (Phi-4-mini-flash):
        a bf16 cache's candidates hold ten and five heads a grid step
        beside two and one (PR 58), a float32 or int8 cache's two at
        most."""
        from benchmarks.kernel_tuning import (_head_block_candidates,
                                              _paged_candidates)
        assert _head_block_candidates(10, 4, jnp.dtype(dtype)) == head_blocks
        assert _paged_candidates(10, 4, jnp.dtype(dtype), 128, 2) == [
            (bk, hb) for bk in (128, 256) for hb in head_blocks]

    def test_decode_attention_sweep_of_the_rings(self):
        """The contiguous decode kernel alone at a ring's layout: every
        (block_k, head block) the dispatcher allows, the entry keyed as
        the dispatch looks it up, each candidate's rows, and at one
        ``block_k`` every head block's result the first one's bit for
        bit."""
        from benchmarks.kernel_tuning import sweep_decode_attention
        said = []
        (key, entry), = sweep_decode_attention(
            3, 40, 16, 256, dtype="bfloat16", kv_heads=10,
            lengths=[256, 0, 77], calls=2, trials=1,
            log=said.append).items()
        assert key == "decode_attention/dma/sq3_sk256_d16_bfloat16_causal"
        swept = entry["swept"]
        assert [(e["block_k"], e["head_block"]) for e in swept] == [
            (bk, hb) for bk in (128, 256) for hb in (10, 5, 2, 1)]
        assert all(e["rows"] == 4 * e["head_block"] and e["same_bits"]
                   for e in swept)
        assert entry["ms"] == min(e["ms"] for e in swept)
        # 333 valid columns of K and V, ten heads of 16 in bf16
        assert entry["bytes_us"] == round(
            2 * 333 * 10 * 16 * 2 / 819e9 * 1e6, 2)
        assert not any("infeasible" in line or "DIFFER" in line
                       for line in said)
        rec = tuning.last_dispatch("decode_attention")["dma"]
        assert (rec["key"], rec["source"], rec["head_block"]) == (
            key, "runtime", 1)
        with pytest.raises(ValueError, match="lengths"):
            sweep_decode_attention(2, 4, 16, 256, lengths=[257], trials=1,
                                   log=lambda *a: None)

    @pytest.mark.parametrize("down,out", [(False, "bfloat16"),
                                          (True, "float32")],
                             ids=["gate-up", "down"])
    def test_grouped_sweep_times_both_arms(self, down, out):
        """XLA's ``ragged_dot`` arm and the kernel at each row tile, at
        one call's shape with a router's uneven groups in one layer of
        the stack; the entry is keyed as the dispatch looks it up."""
        from benchmarks.kernel_tuning import (drawn_groups,
                                              sweep_grouped_matmul)
        sizes = drawn_groups(32, 3, 4, 2)
        assert sizes.sum() == 96 and (sizes[:4] == 0).all()
        (key, entry), = sweep_grouped_matmul(
            32, 3, 4, 2, 128, 256, down=down, out_dtype=out, calls=2,
            trials=1, log=lambda *a: None).items()
        k, n = (256, 128) if down else (128, 256)
        assert key == f"grouped_matmul/groups8/sq96_sk{k}_d{n}_bfloat16_full"
        arms = entry["swept"]
        assert arms[0]["impl"] == "ragged_dot"
        assert [a["block_m"] for a in arms[1:]] == [32, 64]
        assert entry["block_n"] == n and entry["us"] == min(
            a["us"] for a in arms[1:])
        # bf16 x bf16 summed in float32 on both arms
        assert all(a["max_diff"] <= (1e-5 if out == "float32" else 0.02)
                   for a in arms[1:])

    def test_ring_append_sweep_times_the_rows_that_decode(self):
        """The ring's write alone: one entry at the rings' shape, a time a
        count of live rows, each count's first call held to the numpy
        reference inside the sweep."""
        from benchmarks.kernel_tuning import sweep_ring_append
        said = []
        (key, entry), = sweep_ring_append(
            4, 2, 8, 256, dtype="bfloat16", live=(4, 1), calls=2, trials=1,
            log=said.append).items()
        assert key == "b4_h2_d8_w256_bfloat16"
        assert entry["tile"] == 128 and entry["bytes_us_a_row"] > 0
        assert [e["live"] for e in entry["swept"]] == [4, 1]
        assert all(e["us_a_row"] * e["live"] == pytest.approx(e["us"], 0.01)
                   for e in entry["swept"])
        assert len(said) == 2 and "4 live rows" in said[0]
        assert tuning.last_dispatch("ring_append")["tile"]["key"] == key

    @pytest.mark.slow  # fresh-interpreter subprocess (~40s); the sweep
    # plumbing itself is covered in-process above
    def test_bench_cli_kernels_subcommand(self, tmp_path):
        import subprocess
        repo_root = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        out_path = tmp_path / "cli_sweep.json"
        out = subprocess.run(
            [sys.executable, os.path.join(repo_root, "bin", "ds_tpu_bench"),
             "kernels", "--batch", "1", "--heads", "1", "--head-dim", "64",
             "--seq", "128", "--dtype", "float32", "--trials", "1",
             "--max-candidates", "1", "--out", str(out_path)],
            capture_output=True, text=True, timeout=300)
        assert out.returncode == 0, out.stderr[-800:]
        art = json.loads(out_path.read_text())
        assert art["format"] == tuning.FORMAT and art["entries"]
