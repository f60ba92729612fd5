"""The TeraSort cell at a tiny size on the CPU: a sound run is correct, its
control is not, and runs with a fault planted under the timed path are not.
Two of the faults live here, as they are TeraSort's own: a sort that
compares only the first key leaf, and one that moves the keys without their
payload. At the tiny size each key byte here keeps its lowest bit alone, so
records tie on their first 4 and their first 8 key bytes; the job and its
reference both read the records so made."""
import contextlib

import jax
import numpy as np
import pytest

from benchmarks.chip import control, harness, planted
from benchmarks.chip.jobs import terasort

CELL = "terasort-100b.1chip"
TINY = {"records": 1 << 12}
SEED = 2**31 + 2**33 + 29


def _tied(records):
    """``records`` with key bytes of 0 or 1, so that tiny runs hold records
    whose keys tie."""

    def made(seed, n):
        rec = records(seed, n)
        return {**rec, **{k: rec[k] & 0x01010101 for k in ("k0", "k1", "k2")}}

    return made


@pytest.fixture(autouse=True)
def tied_keys(monkeypatch):
    """The job's records tied, in the module the harness loads for each run
    and its control, and in the one imported here."""
    load = harness.load_module

    def load_module(kind, name):
        mod = load(kind, name)
        if (kind, name) == ("jobs", "terasort"):
            mod.records = _tied(mod.records)
        return mod

    monkeypatch.setattr(harness, "load_module", load_module)
    monkeypatch.setattr(terasort, "records", _tied(terasort.records))


def _run(trace=0):
    return harness.run(["--workload", CELL, "--seed", str(SEED), "--seconds", "0.3",
                        "--trace", str(trace)], require_tpu=False, config_overrides=TINY)


@contextlib.contextmanager
def _carry_fault(make):
    from repro.core import shuffle

    orig = shuffle._sort_carry
    shuffle._sort_carry = make(orig)
    try:
        yield
    finally:
        shuffle._sort_carry = orig


def first_leaf_only():
    """Each local sort compares the first key leaf alone; the other leaves
    ride as payload."""

    def make(orig):
        def carry(keys, valid, *trees):
            if not isinstance(keys, tuple):
                return orig(keys, valid, *trees)
            k0, v, rest, *out = orig(keys[0], valid, keys[1:], *trees)
            return ((k0, *rest), v, *out)

        return carry

    return _carry_fault(make)


def keys_without_payload():
    """Each local sort moves the keys and the 1-D leaves, and leaves every
    leaf with trailing dimensions (the payload) where it was."""

    def make(orig):
        def carry(keys, valid, *trees):
            ks, v, *out = orig(keys, valid, *trees)
            out = jax.tree.map(lambda o, x: x if x.ndim > 1 else o, tuple(out), trees)
            return (ks, v, *out)

        return carry

    return _carry_fault(make)


def test_tiny_keys_tie_on_leading_bytes():
    rec = terasort.as_bytes(terasort.records(SEED, TINY["records"]))
    for width in (4, 8):
        lead = rec[:, :width]
        assert len(np.unique(lead, axis=0)) < len(lead)
    assert len(np.unique(rec[:, :10], axis=0)) > 1


def test_layout_reads_back_as_gensort_bytes():
    rec = terasort.records(SEED, 8)
    # the chip hands the (n, 23) payload back in column order
    raw = terasort.as_bytes({k: np.asfortranarray(v) for k, v in rec.items()})
    assert raw.shape == (8, 100)
    k0 = int(np.asarray(rec["k0"])[3])
    assert bytes(raw[3, :4]) == k0.to_bytes(4, "big")
    assert bytes(raw[3, 8:10]) == int(np.asarray(rec["k2"])[3]).to_bytes(2, "big")
    assert bytes(raw[3, 12:16]) == int(np.asarray(rec["payload"])[3, 1]).to_bytes(4, "big")


def test_sound_run_is_correct():
    r = _run()
    assert r["correct"] and r["failed"] == 0 and r["attempted"] >= 1, r
    assert {"records_per_s", "setup_s"} <= set(r["metrics"])  # no memory stats here
    assert r["checks"]["wrong_answers"] == {"value": 0, "limit": 0}


def test_traced_run_reports_the_cells_counter():
    r = _run(trace=1)
    assert r["correct"]
    # the payload, 23 uint32 a record, is gathered after the sort
    assert r["metrics"]["sort_gather_bytes_per_record.terasort"]["value"] == 92.0
    assert r["metrics"]["window_compiles.dataflow"]["value"] == 0


def test_control_fails():
    r = control.readings(CELL, SEED + 1, config_overrides=TINY)
    assert not r["correct"], r


@pytest.mark.parametrize("fault", ["half_batch", "altered_answer", "first_leaf_only",
                                   "keys_without_payload"])
def test_planted_fault_is_not_correct(fault):
    make = {"first_leaf_only": first_leaf_only,
            "keys_without_payload": keys_without_payload}.get(fault) or planted.FAULTS[fault]
    with make():
        r = _run()
    assert not r["correct"], r
