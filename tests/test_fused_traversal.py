"""Fused stage-A traversal kernel: kernel-vs-twin parity + engine lattice.

Contract under test (kernels/fused_traversal.py + core/search.py):

  * ``fused_traversal_round`` (one Pallas pass: ADC lookup, dedup kill,
    bitonic frontier merge, beam selection, mode masks) is **bit-identical**
    to its jnp reference twin ``ref.fused_traversal_round_ref`` on every
    output field — including adversarial batches (duplicate ids, all
    candidates filtered out, M=0 round-0 calls, M not a power of two).
  * ``SearchConfig.use_fused_kernel=True`` produces bit-identical search
    output (ids, dists, every stat) to the unfused loop in all five modes,
    both cache tiers, and every pipeline depth — the flag is a perf knob,
    never a correctness one.
  * ``check_fused_supported`` refuses shapes one program cannot hold
    with an error naming the limit — there is no silent fallback.

Interpret-mode Pallas builds are expensive on CPU, so tier-1 keeps one
mode per lattice axis on a micro index; the full sweep is slow-marked.
"""
import numpy as np
import pytest
import jax.numpy as jnp

from repro.core import EngineConfig, GateANNEngine, SearchConfig
from repro.core import search as searchm
from repro.kernels import fused_traversal as ft
from repro.kernels import ref as kref
from repro.kernels.backend import resolve_interpret, supports_compiled_pallas

MODES = ("gate", "post", "early", "pre_naive", "unfiltered")

# one fixed kernel shape per M so the jitted pallas build is paid once per
# (mode, M) and every adversarial variant below reuses it
B, L, W, C, K, N_IDS = 2, 8, 2, 4, 16, 50
RNG = np.random.default_rng(7)


def _round_inputs(m, *, dup_ids=False, all_filtered=False, seed=None):
    """A plausible mid-search round state (plus adversarial knobs)."""
    rng = np.random.default_rng(RNG.integers(1 << 31) if seed is None else seed)
    fid = rng.choice(N_IDS, size=(B, L), replace=False).astype(np.int32)
    fid[:, L - 2:] = -1  # a couple of empty slots, like a young frontier
    fd = np.where(fid >= 0, rng.random((B, L)).astype(np.float32) * 4,
                  np.float32(3.4e38)).astype(np.float32)
    fexp = (rng.random((B, L)) < 0.3) & (fid >= 0)
    fpas = rng.random((B, L)) < 0.5
    nid = rng.integers(-1, N_IDS, size=(B, m)).astype(np.int32)
    if dup_ids and m >= 2:
        nid[:, 1] = nid[:, 0]  # exact duplicate inside the batch
        nid[:, m - 1] = fid[:, 0]  # and a frontier/candidate collision
    nc = rng.integers(0, K, size=(B, m, C)).astype(np.int32)
    npas = np.zeros((B, m), bool) if all_filtered else rng.random((B, m)) < 0.5
    lut = (rng.random((B, C, K)).astype(np.float32)) * 2
    entry = fid[:, 0].copy()
    return tuple(jnp.asarray(x)
                 for x in (fid, fd, fexp, fpas, nid, nc, npas, lut, entry))


def _assert_round_equal(got, want, ctx):
    for f in got._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(got, f)), np.asarray(getattr(want, f)),
            err_msg=f"{ctx}: FusedRound.{f}",
        )


def _kernel_vs_ref(mode, m, **knobs):
    state = _round_inputs(m, **knobs)
    got = ft.fused_traversal_round(*state, mode=mode, width=W)
    want = kref.fused_traversal_round_ref(*state, mode=mode, width=W)
    _assert_round_equal(got, want, (mode, m, knobs))


@pytest.mark.parametrize("case", ["plain", "dup_ids", "all_filtered"])
def test_kernel_matches_twin_gate(case):
    """Gate mode (the mode with tunnels — every mask populated), main
    shape: plain plus the two adversarial batches that stress the dedup
    kill and the all-tunnel path.  One pallas build serves all three."""
    _kernel_vs_ref("gate", 8, dup_ids=(case == "dup_ids"),
                   all_filtered=(case == "all_filtered"))


@pytest.mark.slow
def test_kernel_round0_m_zero():
    """The pre-loop call: M=0 merges nothing and just selects the first
    beam from the entry-seeded frontier."""
    _kernel_vs_ref("gate", 0)


@pytest.mark.slow
def test_kernel_m_not_power_of_two():
    """L+M=14 exercises the (+INF, -1, seq>=real) pad lanes of the
    bitonic network — pads must sort strictly after real INF entries."""
    _kernel_vs_ref("gate", 6, dup_ids=True)


@pytest.mark.slow
@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("case", ["plain", "dup_ids", "all_filtered",
                                  "m_zero", "m_odd"])
def test_kernel_matches_twin_all_modes(mode, case):
    """Nightly: the full mode x adversarial-case product."""
    m = {"m_zero": 0, "m_odd": 6}.get(case, 8)
    _kernel_vs_ref(mode, m, dup_ids=(case == "dup_ids"),
                   all_filtered=(case == "all_filtered"))


def test_fused_supported_limits(micro_engine, micro_corpus):
    """Shapes past a VMEM ceiling raise an error that names the limit;
    the search loop with use_fused_kernel=True raises it too instead of
    running the unfused loop."""
    ok = dict(l=16, width=2, m=24, k=256)
    ft.check_fused_supported(**ok)
    ft.check_fused_supported(l=64, width=8, m=8 * (32 + 16), k=256)  # smoke
    with pytest.raises(ValueError, match="sort width"):
        ft.check_fused_supported(**{**ok, "l": 4000, "m": 200})
    with pytest.raises(ValueError, match="ADC workspace"):
        ft.check_fused_supported(**{**ok, "k": 16384})
    with pytest.raises(ValueError, match="width >= 1"):
        ft.check_fused_supported(**{**ok, "width": 0})
    with pytest.raises(ValueError, match="M >= 0"):
        ft.check_fused_supported(**{**ok, "m": -1})
    assert ft.sort_width(8, 8) == 128  # one lane tile at least
    assert ft.sort_width(64, 384) == 512
    _, _, queries = micro_corpus
    with pytest.raises(ValueError, match="sort width"):
        micro_engine.search(queries, search_config=SearchConfig(
            mode="unfiltered", search_l=2000, beam_width=2,
            use_fused_kernel=True))


def test_interpret_resolution():
    """interpret=None resolves from the backend (compiled on TPU only);
    explicit bools win."""
    assert supports_compiled_pallas("tpu")
    assert not supports_compiled_pallas("gpu")
    assert not supports_compiled_pallas("cpu")
    assert resolve_interpret(None) == (not supports_compiled_pallas())
    assert resolve_interpret(True) is True
    assert resolve_interpret(False) is False


# ---------------------------------------------------------------------------
# end-to-end engine parity (micro index: interpret-mode builds stay small)
# ---------------------------------------------------------------------------

MICRO_N, MICRO_D = 600, 16


@pytest.fixture(scope="module")
def micro_corpus():
    rng = np.random.default_rng(11)
    vecs = rng.normal(size=(MICRO_N, MICRO_D)).astype(np.float32)
    labels = rng.integers(0, 4, size=MICRO_N).astype(np.int32)
    queries = rng.normal(size=(4, MICRO_D)).astype(np.float32)
    return vecs, labels, queries


@pytest.fixture(scope="module")
def micro_engine(micro_corpus):
    vecs, labels, _ = micro_corpus
    return GateANNEngine.build(
        vecs, labels=labels,
        # shapes chosen so the padded bitonic width stays at 32 lanes
        # (L=12 + W*(degree+r_max)=18 -> 30): interpret-mode pallas build
        # time scales with the network, and this engine serves tier-1
        config=EngineConfig(degree=6, build_l=20, pq_chunks=4, r_max=3),
    )


@pytest.fixture(scope="module")
def micro_index_path(micro_engine, tmp_path_factory):
    path = str(tmp_path_factory.mktemp("fused") / "micro.gann")
    micro_engine.save(path)
    return path


def _cfg(mode, *, fused, depth=1):
    return SearchConfig(mode=mode, search_l=12, beam_width=2,
                        pipeline_depth=depth, use_fused_kernel=fused)


def _filter_for(mode, queries):
    if mode == "unfiltered":
        return None, None
    return "label", np.full(queries.shape[0], 1, np.int32)


def _assert_same(got, want, ctx):
    np.testing.assert_array_equal(np.asarray(got.ids), np.asarray(want.ids),
                                  err_msg=str(ctx))
    np.testing.assert_array_equal(np.asarray(got.dists),
                                  np.asarray(want.dists), err_msg=str(ctx))
    for f in want.stats._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(got.stats, f)),
            np.asarray(getattr(want.stats, f)),
            err_msg=f"{ctx}: stats.{f}",
        )


def test_engine_fused_parity_gate(micro_engine, micro_corpus, monkeypatch):
    """Fused gate search == unfused bit-for-bit, and the fused round
    genuinely ran (trace-time call count — guards a silent fallback)."""
    _, _, queries = micro_corpus
    kind, params = _filter_for("gate", queries)
    calls = []
    real_dispatch = ft.fused_round_for_backend

    def counting_dispatch():
        real = real_dispatch()

        def counting(*args, **kwargs):
            calls.append(1)
            return real(*args, **kwargs)

        return counting

    monkeypatch.setattr(searchm.ftk, "fused_round_for_backend",
                        counting_dispatch)
    want = micro_engine.search(queries, filter_kind=kind, filter_params=params,
                               search_config=_cfg("gate", fused=False))
    assert not calls  # the unfused loop never touches the kernel
    got = micro_engine.search(queries, filter_kind=kind, filter_params=params,
                              search_config=_cfg("gate", fused=True))
    assert calls  # traced through the fused path, no silent fallback
    _assert_same(got, want, ("gate", "fused", "memory-tier"))


def test_engine_config_plumbs_fused_default(micro_index_path, micro_corpus,
                                            monkeypatch):
    """EngineConfig.use_fused_kernel survives save/load and becomes the
    SearchConfig default only when the caller passes no config (an
    explicit search_config always wins).  Captured at the filtered_search
    boundary — no search actually runs."""
    import dataclasses

    _, _, queries = micro_corpus
    eng = GateANNEngine.load(micro_index_path)
    assert eng.config.use_fused_kernel is False  # default survived the disk
    fused_eng = dataclasses.replace(
        eng, config=dataclasses.replace(eng.config, use_fused_kernel=True)
    )
    seen = []

    def capture(**kwargs):
        seen.append(kwargs["config"])
        raise RuntimeError("captured")

    monkeypatch.setattr(searchm, "filtered_search", capture)
    kind, params = _filter_for("gate", queries)
    for engine, explicit, want_flag in (
        (eng, None, False),  # engine default off
        (fused_eng, None, True),  # engine default on -> SearchConfig on
        (fused_eng, _cfg("gate", fused=False), False),  # explicit cfg wins
    ):
        with pytest.raises(RuntimeError, match="captured"):
            engine.search(queries, filter_kind=kind, filter_params=params,
                          search_config=explicit)
        assert seen[-1].use_fused_kernel is want_flag


@pytest.mark.slow
def test_engine_fused_lattice_disk(micro_index_path, micro_corpus):
    """Nightly: 5 modes x pipeline_depth {1, 2, 4} on the disk tier —
    fused pinned bit-identical to unfused everywhere."""
    _, _, queries = micro_corpus
    eng = GateANNEngine.load(micro_index_path, store_tier="disk")
    for mode in MODES:
        kind, params = _filter_for(mode, queries)
        want = eng.search(queries, filter_kind=kind, filter_params=params,
                          search_config=_cfg(mode, fused=False))
        for depth in (1, 2, 4):
            got = eng.search(queries, filter_kind=kind, filter_params=params,
                             search_config=_cfg(mode, fused=True, depth=depth))
            _assert_same(got, want, (mode, depth, "disk"))
    eng.record_store.close()


@pytest.mark.slow
@pytest.mark.parametrize("policy", ("visit_freq", "adaptive"))
@pytest.mark.parametrize("mode", ("gate", "post"))
def test_engine_fused_lattice_cache(micro_index_path, micro_corpus, mode,
                                    policy):
    """Nightly: fused parity through both cache tiers (the cached-mask
    split runs outside the kernel — stats must still reconcile exactly)."""
    _, _, queries = micro_corpus
    eng = GateANNEngine.load(micro_index_path, store_tier="disk")
    cached = eng.with_cache(24 * 4096, policy=policy, refresh_every=0)
    kind, params = _filter_for(mode, queries)
    want = cached.search(queries, filter_kind=kind, filter_params=params,
                         search_config=_cfg(mode, fused=False))
    for depth in (1, 4):
        got = cached.search(queries, filter_kind=kind, filter_params=params,
                            search_config=_cfg(mode, fused=True, depth=depth))
        _assert_same(got, want, (mode, policy, depth, "cached"))
    eng.record_store.close()
