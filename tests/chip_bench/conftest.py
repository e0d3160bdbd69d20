"""Two tests of this directory pin what ISSUE 33 changes, in files a
``model_config`` PR may not edit (they are the benchmark's: ``paths`` in
``BENCHMARK.json``). They are marked as expected failures here, strictly
and by name, so that the run says why they fail instead of going red —
and so that the ``benchmark`` PR that mends them (ROADMAP 2.8a) has to
take this file away: a strict expected failure that passes is an error.

- ``test_olmoe_cell.py`` holds ``serve_tokens_per_s``' ``workloads`` to
  exactly two cells; ISSUE 33 appends ``serve-lfm2-agent``. Mend: compare
  the first two.
- ``test_traffic.py`` holds every mix to prompt + output <= 2048, the
  ``max_len`` of the cells PR 23 had; ISSUE 33's ``agent-closed-32``
  goes to 3072 + 256 in slots of 4096. Mend: bound a mix by the
  ``max_len`` of the configurations that run it."""

import pytest

PINNED_BEFORE_ISSUE_33 = {
    "test_olmoe_cell.py::test_the_cell_is_in_the_manifest_as_the_issue_has_it":
        "serve_tokens_per_s lists a third cell since ISSUE 33",
    "test_traffic.py::test_lengths_stay_inside_the_mix_and_the_server"
    "[agent-closed-32]":
        "agent-closed-32 runs in slots of 4096, not 2048 (ISSUE 33)",
}


def pytest_collection_modifyitems(items):
    for item in items:
        for tail, reason in PINNED_BEFORE_ISSUE_33.items():
            if item.nodeid.endswith("tests/chip_bench/" + tail):
                item.add_marker(pytest.mark.xfail(reason=reason, strict=True))
