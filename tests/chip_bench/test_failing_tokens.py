"""What decides ``correct`` of a serving run and what a failure leaves
behind (``serve_runner.decide``, PR 54), at the rehearsal's size on the
CPU and through the run's own function: a sound program's numbers
beside their limits; a token altered where it is served is the one
token named, with its seed, request, position and the reference's
closest router choice there; nothing serves a request again and nothing
pardons a token over the limit; a family whose reference leaves no
near-tie unjudged says so by a None. And which rows the two float32
cells leave unjudged: the near-ties, and once one lies upstream the
choices under ``CARRIED_TIE`` (``family.unjudged``; the probe its band is
set from rehearses)."""

import gc
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmarks.chip import families, manifest as manifest_mod
from benchmarks.chip import model as bench_model
from benchmarks.chip import reference, serve_runner, traffic

from ._paths import BENCH, PYTHONPATH, ROOT
from .test_control import CONFIG as GPT2_CONFIG, _greedy, _weights

CELLS = {"lfm2": "serve-lfm2-agent", "deepseek_v3": "serve-kanana-docqa"}
SEED = 2 ** 31 + 5401


class Served:
    """One family's cell at the rehearsal's size: module, weights, and
    the first requests of its mix served to their end by a plain server,
    as ``serve_runner.run`` hands them to ``decide``."""

    def __init__(self, family_name):
        import jax.numpy as jnp
        import deepspeed_tpu as ds
        cell = manifest_mod.Cell(ROOT, manifest_mod.load(ROOT),
                                 CELLS[family_name])
        self.config = cell.config
        self.family = families.load(self.config)
        assert self.family.__name__.endswith(family_name)
        self.mix = traffic.resolve(cell.traffic, True)
        self.sizes = self.family.sizes(self.config, True)
        self.serving = self.config["rehearse"]["serving"]
        self.module = self.family.build(self.config, True)
        self.params = bench_model.seeded_params(self.module, SEED)
        srv = ds.init_inference(
            self.module, params=self.params,
            dtype=getattr(jnp, self.config["compute_dtype"])
        ).serve(dict(self.serving))
        stream = traffic.RequestStream(self.mix, SEED,
                                       self.sizes["vocab_size"])
        self.records = [serve_runner.Record(stream.take(), 0.0)
                        for _ in range(serve_runner.CHECKED_REQUESTS)]
        for rec in self.records:
            serve_runner.submit(srv, rec)
        srv.run()
        srv.close()
        del srv
        gc.collect()
        self.said = []

    def decide(self, records=None):
        del self.said[:]
        return serve_runner.decide(
            self.family, self.params, self.sizes, self.config,
            self.serving["max_len"], self.mix, records or self.records,
            SEED, self.said.append)


@pytest.fixture(scope="module", params=sorted(CELLS))
def served(request):
    return Served(request.param)


def test_a_sound_run_is_correct_and_says_each_number_beside_its_limit(
        served):
    out = served.decide()
    numbers = out["numbers"]
    assert out["held"] is True and out["failures"] == []
    assert list(numbers) == ["tokens_checked", "logit_gap_sigma",
                             "tokens_over"]
    assert numbers["tokens_over"] == {"value": 0, "limit": 0}
    assert numbers["logit_gap_sigma"] == {
        "value": out["tokens"]["max"], "limit": serve_runner.LOGIT_TOL_SIGMA}
    assert numbers["tokens_checked"]["value"] == sum(
        len(r.handle.output_tokens) for r in served.records) > 0


def _altered(rec, at, vocab):
    """``rec`` with the ``at``-th token it was served replaced by the
    next of the vocabulary: a token altered where it is produced."""
    output = np.array(rec.handle.output_tokens, np.int32)
    output[at] = output[at] % (vocab - 1) + 1
    return _Served(rec.spec, output)


class _Served:
    def __init__(self, spec, output):
        self.spec = spec
        self.handle = type("Handle", (), {"output_tokens": output})()


def test_an_altered_token_is_not_correct_and_is_the_token_named(served):
    """The fault of the contract's step 3 that a serving cell can have.
    The tokens after it are the program's on another history, so more
    than one may read over the limit: the first named is the altered
    one, and nothing before it fails."""
    records = list(served.records)
    which = int(np.argmax([len(r.handle.output_tokens) for r in records]))
    at = len(records[which].handle.output_tokens) // 2
    records[which] = _altered(records[which], at,
                              served.sizes["vocab_size"])
    out = served.decide(records)
    numbers, first = out["numbers"], out["failures"][0]
    assert out["held"] is False
    assert numbers["tokens_over"]["value"] == len(out["failures"]) >= 1
    assert numbers["tokens_over"]["limit"] == 0
    assert numbers["logit_gap_sigma"]["value"] > serve_runner.LOGIT_TOL_SIGMA
    spec = served.records[which].spec
    assert first["seed"] == SEED and first["request"] == spec["id"]
    assert first["prompt_len"] == len(spec["prompt"])
    assert first["position"] == len(spec["prompt"]) + at
    assert first["shared_prefix"] == serve_runner._shared_tokens(
        spec, served.mix) > 0
    assert first["gap_sigma"] == out["tokens"]["gaps"][which][at]
    assert all(f["request"] == spec["id"] for f in out["failures"])
    # the reference's closest router choice at the row that predicted it
    assert 0 <= first["choice_gap"] < 1
    assert first["near_ties_in_request"] >= 0
    worst = max(out["failures"], key=lambda f: f["gap_sigma"])
    assert numbers["worst_token_position"] == {"value": worst["position"]}
    assert numbers["worst_token_request"] == {"value": spec["id"]}
    assert numbers["worst_token_choice_gap"] == {
        "value": worst["choice_gap"]}
    assert sum(s.startswith("token over the limit: seed")
               for s in served.said) == len(out["failures"])


def test_no_token_over_the_limit_is_pardoned_and_nothing_is_served_again(
        served, monkeypatch):
    """A sound program with every token forced over the limit (all are
    the reference's argmax at this size: gap 0, limit below it) is not
    correct: no second reading of another server's rows stands in for
    the window's tokens (REVIEW of PR 54)."""
    import deepspeed_tpu as ds
    monkeypatch.setattr(serve_runner, "LOGIT_TOL_SIGMA", -1.0)

    def refuse(*_a, **_k):
        raise AssertionError("a server was built to decide `correct`")
    monkeypatch.setattr(ds, "init_inference", refuse)
    out = served.decide()
    numbers = out["numbers"]
    assert out["held"] is False
    assert numbers["tokens_over"]["value"] == numbers["tokens_checked"][
        "value"] == len(out["failures"])
    assert any(f"and {len(out['failures']) - serve_runner.SAID_TOKENS} more"
               in s for s in served.said) or len(
                   out["failures"]) <= serve_runner.SAID_TOKENS


def test_the_gaps_are_what_the_unjudged_rows_are_cut_from(served,
                                                          monkeypatch):
    """``near_ties="gaps"`` gives the rows as ``"kept"`` does, and the
    rows that come back as zeros for ``correct`` are ``family.unjudged``
    of those gaps: the positions under ``NEAR_TIE``, once one lies
    upstream those under ``CARRIED_TIE``, and what LFM2's convolutions
    carry either into."""
    import jax
    import jax.numpy as jnp
    family, rec = served.family, served.records[0]
    ids = np.concatenate([rec.spec["prompt"], rec.handle.output_tokens])
    ids = jnp.asarray(ids[None], jnp.int32)
    gaps = serve_runner._choice_gaps(
        family, served.params, served.sizes, served.config, ids.shape[1])(
            np.asarray(ids[0]))
    # bands wide enough that some positions of this request are in each
    monkeypatch.setattr(family, "NEAR_TIE", float(np.quantile(gaps, 0.05)))
    monkeypatch.setattr(family, "CARRIED_TIE", float(np.quantile(gaps, 0.2)))
    with reference.highest():
        mode = lambda m: jax.jit(lambda p, x: family.reference_logits(
            p, x, served.sizes, served.config, near_ties=m))(
                served.params, ids)
        kept, (rows, gap), judged = mode("kept"), mode("gaps"), mode(
            "unjudged")
    assert np.array_equal(np.asarray(rows), np.asarray(kept))
    assert np.array_equal(np.asarray(gap[0]), gaps)
    zeroed = ~np.asarray(judged[0]).any(-1)
    assert np.array_equal(zeroed, _unjudged(family, gaps, served.sizes))
    assert np.array_equal(np.asarray(judged[0])[~zeroed],
                          np.asarray(kept[0])[~zeroed])
    near = gaps < family.NEAR_TIE
    assert near.sum() < zeroed.sum() < len(gaps)    # the band took some


def _unjudged(family, gaps, sizes):
    """The rule in plain numpy, a position at a time."""
    near = gaps < family.NEAR_TIE
    mask = np.array([near[i] or (near[:i].any()
                                 and gaps[i] < family.CARRIED_TIE)
                     for i in range(len(gaps))])
    reach = family._reach(sizes) if hasattr(family, "_reach") else 0
    return np.array([mask[max(0, i - reach):i + 1].any()
                     for i in range(len(mask))])


@pytest.mark.parametrize("name", sorted(CELLS))
def test_a_close_choice_is_unjudged_only_once_a_near_tie_lies_upstream(name):
    """What PR 54 found on the chip: a choice 1.3e-5 apart (Kanana) and
    one 2.8e-5 apart (LFM2) fell the other way after near-ties upstream.
    With none upstream such a choice is float32-safe and stays judged."""
    from benchmarks.chip.families import deepseek_v3, lfm2
    family = {"lfm2": lfm2, "deepseek_v3": deepseek_v3}[name]
    assert family.NEAR_TIE == 1e-5 < family.CARRIED_TIE <= 1e-3
    sizes = {"conv_L_cache": 3}
    close, tie, clear = family.CARRIED_TIE / 2, family.NEAR_TIE / 2, 1e-2
    gaps = np.array([clear, close, clear, tie, clear, close] + [clear] * 6,
                    np.float32)
    got = np.asarray(family.unjudged(gaps[None], sizes))[0]
    assert np.array_equal(got, _unjudged(family, gaps, sizes))
    assert not got[:3].any() and got[3] and got[5]   # 1: nothing upstream
    if name == "deepseek_v3":
        assert got.sum() == 2
    else:       # and the four positions after each
        assert got[3:10].all() and not got[10:].any()
    # no near-tie anywhere: every close choice is judged
    none = np.where(gaps < family.NEAR_TIE, clear, gaps)
    assert not np.asarray(family.unjudged(none[None], sizes)).any()


@pytest.mark.parametrize("name", sorted(CELLS))
def test_the_probe_the_bands_are_set_from_rehearses(name, tmp_path):
    """``tools/near_tie_probe.py`` holds together on the CPU: the
    reference's choice gaps over whole sequences, the control served,
    each candidate band's cost and count written out."""
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "tools", "near_tie_probe.py"),
         "--workload", CELLS[name], "--seeds", "1", "--sequences", "2",
         "--control", "1", "--rehearse", "--out", str(tmp_path)],
        capture_output=True, text=True, timeout=900,
        env=dict(os.environ, PYTHONPATH=PYTHONPATH))
    assert proc.returncode == 0, proc.stderr[-2000:]
    (written,) = os.listdir(tmp_path)
    with open(tmp_path / written) as f:
        out = json.load(f)
    bands = {"0.0", "3e-05", "0.0001", "0.0003"}
    assert set(out["unjudged_share_by_band"]) == bands
    shares = [out["unjudged_share_by_band"][b] for b in sorted(
        bands, key=float)]
    assert shares == sorted(shares) and 0 <= shares[0] <= shares[-1] < 1
    assert out["positions"] == sum(s["positions"] for s in out["sequences"])
    assert set(out["bf16_activations"]["over_the_limit_by_band"]) == bands


def test_a_family_without_near_ties_names_its_tokens_without_them():
    from benchmarks.chip.families import falcon_h1, gpt2, olmoe
    assert not any(hasattr(f, "NEAR_TIE") for f in (gpt2, olmoe, falcon_h1))
    sizes, params = _weights(SEED)
    _, other = _weights(SEED + 100)
    rng = np.random.default_rng(SEED)
    records = []
    for i in range(2):
        prompt = rng.integers(1, sizes["vocab_size"], size=16, dtype=np.int32)
        rec = _Served({"prompt": prompt, "id": i, "kind": "plain"},
                      _greedy(other if i else params, prompt, 6, sizes))
        records.append(rec)
    said = []
    out = serve_runner.decide(
        gpt2, params, sizes, GPT2_CONFIG, GPT2_CONFIG["n_positions"], {},
        records, SEED, said.append)
    assert out["held"] is False
    # another model's tokens fail, the model's own do not
    assert {f["request"] for f in out["failures"]} == {1}
    assert all(f["near_tie_back"] is None and f["choice_gap"] is None
               and f["shared_prefix"] == 0 for f in out["failures"])
    assert set(out["numbers"]) == {
        "tokens_checked", "logit_gap_sigma", "tokens_over",
        "worst_token_request", "worst_token_position"}
