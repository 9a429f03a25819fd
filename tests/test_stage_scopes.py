"""The search loop's stage names reach the compiled program.

``core/search.py`` runs its pieces under ``jax.named_scope`` names that a
profiler trace reads back from each op's ``op_name`` metadata
(``bench/stages.py``).  Each loop variant's compiled HLO must carry every
one of its stage names, or a stage's device time falls silent."""
import re

import numpy as np
import pytest

from repro.core import GateANNEngine, SearchConfig
from repro.core import search as searchm

UNFUSED = {"select", "adc", "visited", "merge", "fetch", "rerank"}


@pytest.fixture(scope="module")
def disk_engine(tiny_engine, tmp_path_factory):
    path = str(tmp_path_factory.mktemp("scopes") / "idx.gann")
    tiny_engine.save(path)
    engine = GateANNEngine.load(path, store_tier="disk")
    yield engine
    engine.measured_store().close()


@pytest.mark.parametrize("depth,fused,stages", [
    (1, False, UNFUSED),
    (2, False, UNFUSED),
    (1, True, UNFUSED - {"merge"} | {"fused_round"}),
    (2, True, UNFUSED - {"merge"} | {"fused_round"}),
], ids=["sync", "pipelined", "fused-sync", "fused-pipelined"])
def test_compiled_search_carries_each_stage_name(disk_engine, tiny_corpus,
                                                 monkeypatch, depth, fused, stages):
    _, _, queries = tiny_corpus
    texts = []
    search = searchm.filtered_search

    def compiled_text(**kw):
        texts.append(search.lower(**kw).compile().as_text())
        return search(**kw)

    monkeypatch.setattr(searchm, "filtered_search", compiled_text)
    out = disk_engine.search(
        queries[:4], filter_kind="label", filter_params=np.zeros(4, np.int32),
        search_config=SearchConfig(mode="gate", search_l=16, beam_width=4,
                                   pipeline_depth=depth, use_fused_kernel=fused))
    np.asarray(out.ids)
    (text,) = texts
    found = {part for name in re.findall(r'op_name="([^"]*)"', text)
             for part in name.split("/")}
    assert stages <= found, stages - found
    if not fused:
        assert "fused_round" not in found
