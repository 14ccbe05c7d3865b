"""The environment variables the package reads, as a literal: a new
knob is a visible edit of this list (ROADMAP D3 wants it shorter)."""

import pathlib
import re

import elasticsearch_tpu

ES_TPU_NAMES = [
    "ES_TPU_ANN_MIN_DOCS", "ES_TPU_AUTOTUNE_REPS",
    "ES_TPU_COALESCE_WINDOW_MS", "ES_TPU_DELTA_PACK",
    "ES_TPU_DEVICE_BUILD", "ES_TPU_FAULT_INJECT", "ES_TPU_FUSED",
    "ES_TPU_FUSED_BACKEND", "ES_TPU_MESH_STEPPED", "ES_TPU_PACK_DISPATCH",
    "ES_TPU_PALLAS", "ES_TPU_PALLAS_COVERAGE", "ES_TPU_POSITIONAL",
    "ES_TPU_RACE_GUARD", "ES_TPU_RESIDENT_LOOP",
    "ES_TPU_TIERED_BUDGET_BYTES", "ES_TPU_TIERED_CHUNK_TILES",
    "ES_TPU_TIERED_PACK", "ES_TPU_TRACE_GUARD"]


def test_the_package_names_these_variables_and_no_other():
    root = pathlib.Path(elasticsearch_tpu.__file__).parent
    found = set()
    for path in root.rglob("*.py"):
        found.update(re.findall(r"ES_TPU_[A-Z0-9_]+", path.read_text()))
    assert sorted(found) == ES_TPU_NAMES
    assert len(ES_TPU_NAMES) == 19
