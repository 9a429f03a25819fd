"""Stage scopes and host waits of a trace (``bench/stages.py``), on
hand-made traces and on traces recorded on a TPU v5e (``data/``), and the
reader of ``disk.callback_host_ms_per_round``."""
import glob
import importlib.util
import json
import os
import sys
import types

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import stages, trace  # noqa: E402

MS = 1e6  # ns
DATA = os.path.join(os.path.dirname(__file__), "data")
RECV = ("%io_callback.9 = (f32[4,2,8]{2,1,0}, token[]) recv-done(%x), "
        "channel_id=1, is_host_transfer=true")


def op(name, start_ms, dur_ms, scope=""):
    return [name, start_ms * MS, dur_ms * MS, scope]


def hand_made():
    """One round: ADC and a select op, a 30-ms wait for the drain's
    result, a merge op, and an op in no scope, under a ``while``."""
    dev = [op("%while.1 = () while(%t)", 0, 100),
           op("%fusion.1 = f32[8]{0} fusion(%a)", 0, 15, "jit(f)/while/body/adc/gather:"),
           op("%fusion.2 = s32[8]{0} fusion(%b)", 15, 5, "jit(f)/while/body/select/fetch/and:"),
           op(RECV, 20, 30, "jit(f)/while/body/fetch/io_callback:"),
           op("%sort.3 = f32[4,9]{1,0} sort(%c)", 50, 10, "jit(f)/while/body/merge/sort:"),
           op("%copy.4 = f32[8]{0} copy(%d)", 60, 5, "jit(f)/copy:"),
           op("%copy.5 = f32[8]{0} copy(%d)", 65, 5)]
    host = {
        "python3": [["bench.retrieve", 0.0, 100 * MS], ["engine.search", 1 * MS, 98 * MS]],
        "TpuHostTransfer": [["disk.drain", 25 * MS, 15 * MS],
                            ["disk.drain_wait", 30 * MS, 5 * MS]],
        "TpuHostTransferManagerRecvThread/1": [
            ["tpu::System::TransferToDevice", 40 * MS, 5 * MS],
            ["Linearize", 41 * MS, 2 * MS]],
    }
    return {"devices": {"/device:TPU:0": [e[:3] + [e[3]] for e in dev]}, "host": host}


def test_stage_is_the_first_stage_name_of_the_stack():
    assert stages.stage("jit(filtered_search)/while/body/adc/gather:") == "adc"
    assert stages.stage("jit(f)/while/body/select/fetch/and:") == "select"
    assert stages.stage("jit(f)/while/body/fused_round/pallas_call:") == "fused_round"
    assert stages.stage("jit(f)/while/body/gather:") == "unscoped"
    assert stages.stage("") == "unscoped"


def test_reduce_by_hand():
    red = stages.reduce(hand_made(), window_ns=(0.0, 100 * MS), offset_ns=0.0)
    # innermost work only: the while and the wait are no busy time
    assert dict(red["device_scopes"]) == pytest.approx({
        "adc": 0.015, "select": 0.005, "merge": 0.010, "unscoped": 0.010})
    # the 30-ms wait: the drain body, the read it waited for inside it,
    # the runtime's transfer of the result (Linearize nested in it), and
    # no host event at all; bench.retrieve and engine.search cover the
    # whole wait, so they are its callers and claim none of it
    assert dict(red["host_waits"]) == pytest.approx({
        "disk.drain": 0.010, "disk.drain_wait": 0.005,
        "tpu::System::TransferToDevice": 0.003, "Linearize": 0.002,
        stages.NOTHING: 0.010})
    assert sum(v for _, v in red["host_waits"]) == pytest.approx(0.030)
    assert red["host_offset_ns"] == 0.0


def test_reduce_averages_over_device_planes():
    tr = hand_made()
    tr["devices"]["/device:TPU:1"] = tr["devices"]["/device:TPU:0"]
    one = stages.reduce(hand_made(), window_ns=(0.0, 100 * MS), offset_ns=0.0)
    two = stages.reduce(tr, window_ns=(0.0, 100 * MS), offset_ns=0.0)
    assert dict(two["device_scopes"]) == pytest.approx(dict(one["device_scopes"]))
    assert dict(two["host_waits"]) == pytest.approx(dict(one["host_waits"]))


def test_callback_transit_by_hand():
    # 30 ms of wait, 15 of it under disk.drain: 15 ms of crossing
    assert stages.callback_transit_s(hand_made(), window_ns=(0.0, 100 * MS),
                                     offset_ns=0.0) == pytest.approx(0.015)
    # with the host's events moved back 10 ms, the drain covers [15, 30)
    assert stages.callback_transit_s(hand_made(), window_ns=(0.0, 100 * MS),
                                     offset_ns=10 * MS) == pytest.approx(0.020)


SEND = ("%io_callback.8 = token[] send-done((s32[]{:T(128)}, token[]) %s), "
        "channel_id=2, is_host_transfer=true")


def test_host_offset_from_operand_transfers():
    """Rounds of uneven length, each with two operands sent to the host;
    the host records receiving each 3 ms after the chip's send-done ends
    (the timeline's offset) and 20 us of jitter.  The pairing of each
    receipt with the send of another round agrees on no one offset."""
    rng = np.random.default_rng(0)
    starts = np.cumsum(rng.uniform(20, 60, size=40))  # ms
    dev, recvs = [], []
    for t in starts:
        for k in range(2):
            a = t + 0.3 * k
            dev.append(op(SEND, a, 0.2))
            recvs.append(["tpu::System::TransferFromDevice",
                          (a + 0.2 + 3.0) * MS + rng.uniform(-20e3, 20e3), 30e3])
        dev.append(op(RECV, t + 0.6, 5))
    tr = {"devices": {"/device:TPU:0": dev},
          "host": {"futex": recvs, "python3": [["bench.retrieve", 0.0, 3e9]]}}
    assert stages.host_offset_ns(tr) == pytest.approx(3.0 * MS, abs=0.03 * MS)
    assert stages.reduce(tr)["host_offset_ns"] == pytest.approx(3.0 * MS, abs=0.03 * MS)
    # no operand transfer: no shift
    assert stages.host_offset_ns(hand_made()) == 0.0


def test_traced_rounds_by_hand():
    call = types.SimpleNamespace
    calls = [call(t0=0.0, t1=2.0, stats={"n_hops": np.array([10, 40])}),
             call(t0=2.0, t1=3.0, stats={"n_hops": np.array([30, 5])}),
             call(t0=5.0, t1=6.0, stats={"n_hops": np.array([99])})]
    # half of the first call (40 rounds) and all of the second (30)
    assert stages.traced_rounds(calls, 1.0, 3.0) == pytest.approx(50.0)


def test_load_matches_profile_data_on_a_host_trace(tmp_path):
    """On the CPU there is no device plane; the host lines ``load`` reads
    itself must equal ``jax.profiler.ProfileData``'s."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.sort(jnp.sin(x)))
    f(jnp.ones(64)).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("disk.drain"):
        f(jnp.ones(64)).block_until_ready()
    jax.profiler.stop_trace()
    path = trace.find(str(tmp_path))
    ours, theirs = stages.load(path), trace.load(path)
    assert ours["devices"] == theirs["devices"] == {}
    assert ours["host"] == theirs["host"]
    assert any(e[0] == "disk.drain" for evs in ours["host"].values() for e in evs)


# -- recorded traces ------------------------------------------------------

RECORDED = sorted(glob.glob(os.path.join(DATA, "trace_*.json")))


@pytest.mark.parametrize("path", RECORDED, ids=[os.path.basename(p) for p in RECORDED])
def test_recorded_trace_reduction_is_unchanged(path):
    """``bench/trace.py`` reads the recorded traces as it first did."""
    with open(os.path.join(DATA, "reduced_trace_expected.json")) as f:
        want = json.load(f)[os.path.basename(path)]
    with open(path) as f:
        rec = json.load(f)
    red = trace.reduce(rec, window_ns=tuple(rec["window_ns"]))
    assert red["idle_share"] == want["idle_share"]
    assert red["busy_s"] == want["busy_s"]
    assert red["device_ops"] == [list(kv) for kv in want["device_ops"]]
    assert red["idle_gaps"] == [list(kv) for kv in want["idle_gaps"]]


SCOPED = sorted(glob.glob(os.path.join(DATA, "stages_*.json")))


@pytest.mark.parametrize("path", SCOPED, ids=[os.path.basename(p) for p in SCOPED])
def test_recorded_scoped_trace(path):
    """A trace recorded with the stage scopes and the callback spans: the
    stages hold nine tenths of the busy time or more and add up to it,
    and the host waits add up to the chip's waits on the host."""
    with open(path) as f:
        rec = json.load(f)
    window = tuple(rec["window_ns"])
    red = trace.reduce(rec, window_ns=window)
    st = stages.reduce(rec, window_ns=window, offset_ns=rec["host_offset_ns"])
    scopes = dict(st["device_scopes"])
    assert sum(scopes.values()) == pytest.approx(red["busy_s"], rel=1e-9)
    assert scopes.get(stages.UNSCOPED, 0.0) <= 0.1 * red["busy_s"]
    waits = sum(v for k, v in red["idle_gaps"] if k.startswith("waits on host"))
    assert sum(v for _, v in st["host_waits"]) == pytest.approx(waits, rel=0.02, abs=1e-6)


# -- the reader ---------------------------------------------------------------

def _reader(name):
    spec = importlib.util.spec_from_file_location(
        "reader", os.path.join(ROOT, "bench", "metrics", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _run(tier="disk", spans=None, trace_=True):
    call = types.SimpleNamespace
    calls = [call(t0=0.0, t1=4.0, stats={"n_hops": np.array([100, 20])}),
             call(t0=4.0, t1=6.0, stats={"n_hops": np.array([50])})]
    tr = None
    if trace_:
        tr = {"t0": 2.0, "t1": 6.0, "spans": spans if spans is not None else {
            "disk.submit": 0.6, "disk.drain": 0.9, "disk.drain_wait": 0.5,
            "disk.preadv": 2.0, "engine.search": 4.0}}
    return types.SimpleNamespace(config={"record_tier": {"tier": tier}},
                                 calls=calls, trace=tr)


@pytest.mark.parametrize("run,want", [
    (_run(), 1e3 * 1.5 / 100.0),  # (0.6 + 0.9) s over 50 + 50 rounds
    (_run(spans={"disk.fetch": 0.3}), 1e3 * 0.3 / 100.0),
    (_run(trace_=False), None),
    (_run(tier="memory"), None),
    # a program whose spans cover only part of a callback reads nothing
    (_run(spans={"disk.submit": 0.6, "disk.drain_wait": 0.5}), None),
], ids=["pipelined", "sync", "no-trace", "memory-tier", "no-whole-callback-span"])
def test_callback_host_reader(run, want):
    got = _reader("disk.callback_host_ms_per_round")(run)
    assert got == (pytest.approx(want) if want is not None else None)
