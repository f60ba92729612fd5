"""reduceByKey keeps each key's total on its segment's last row.

The inclusive segmented scan already holds a segment's total on its last
row, so both tiers of the reduce post hook (``make_reduce_post`` over
``segmented_reduce``, ``make_reduce_post_kernel`` over ``segment_totals``)
keep that row: no gather of the totals back to the heads and no reverse
cummin to find the segment ends. These tests pin the lowered program, the
kept rows, and the totals (integers exact, float32 bit-equal to the tier's
own forward scan) on one executor and, in a subprocess, on four.
"""
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import shuffle as sh
from _reduce_tails import BLOCK, CASES, check_reduce_by_key, forward_scan

IDENT = 41  # nonzero, so an identity that leaked into a total would show


def _post(tier):
    if tier == "kernel":
        return sh.make_reduce_post_kernel("sum", IDENT, block=BLOCK,
                                          interpret=True)
    return sh.make_reduce_post(jnp.add, IDENT)


@pytest.mark.parametrize("tier,dtype", CASES)
def test_reduce_post_lowers_without_gather_or_reverse(tier, dtype):
    n = 4096
    keys = jnp.zeros(n, jnp.int32)
    data = {"key": keys, "value": jnp.zeros(n, dtype)}
    text = jax.jit(_post(tier)).lower(keys, jnp.ones(n, bool), data).as_text()
    ops = set(re.findall(r"stablehlo\.([a-z_]+)", text))
    assert "add" in ops  # the scan is there
    assert "gather" not in ops and "reverse" not in ops, sorted(ops)


def _segments(keys, valid):
    """NumPy: the (start, end) rows of every maximal run of valid equal keys."""
    out, start = [], None
    for i in range(len(keys)):
        if not valid[i]:
            start = None
            continue
        if start is None or keys[i] != keys[i - 1]:
            start = i
        if i == len(keys) - 1 or not valid[i + 1] or keys[i + 1] != keys[i]:
            out.append((start, i))
    return out


@pytest.mark.parametrize("tier,dtype", CASES)
def test_reduce_post_keeps_last_rows_with_interleaved_invalid_rows(tier, dtype):
    rng = np.random.default_rng(7)
    n = 300
    keys = np.sort(rng.integers(0, 20, n)).astype(np.int32)
    valid = rng.random(n) < 0.7  # invalid rows inside runs split them
    valid[-1] = True  # a segment that ends on the last row
    vals = (rng.integers(-1000, 1000, n) if dtype == "int32"
            else rng.standard_normal(n)).astype(dtype)
    data = {"key": jnp.asarray(keys), "value": jnp.asarray(vals)}
    out, kept = jax.jit(_post(tier))(jnp.asarray(keys), jnp.asarray(valid), data)
    kept, got = np.asarray(kept), np.asarray(out["value"])
    segs = _segments(keys, valid)
    want_kept = np.zeros(n, bool)
    want_kept[[e for _, e in segs]] = True
    assert np.array_equal(kept, want_kept)
    assert np.array_equal(np.asarray(out["key"])[kept], keys[[s for s, _ in segs]])
    assert (got[~valid] == IDENT).all()  # invalid rows hold the identity
    if dtype == "int32":
        assert np.array_equal(got[kept], [vals[s:e + 1].sum() for s, e in segs])
    else:
        ref = np.asarray(forward_scan(tier, jnp.asarray(keys), jnp.asarray(valid),
                                      jnp.asarray(vals)))
        assert got[kept].tobytes() == ref[kept].tobytes()


@pytest.mark.parametrize("tier,dtype", CASES)
def test_reduce_by_key_keeps_last_rows_one_executor(tier, dtype):
    assert check_reduce_by_key(1, tier, dtype) == []


@pytest.fixture(scope="module")
def four_executors():
    """The cases' lines from one run of ``_reduce_tails.py`` on four host
    devices."""
    here = os.path.dirname(__file__)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(here, "..", "src"), here, env.get("PYTHONPATH", "")])
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, os.path.join(here, "_reduce_tails.py")],
                       env=env, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-3000:]}"
    return dict(line.split(": ", 1) for line in r.stdout.splitlines()
                if ": " in line)


@pytest.mark.parametrize("tier,dtype", CASES)
def test_reduce_by_key_keeps_last_rows_four_executors(four_executors, tier, dtype):
    assert four_executors[f"{tier}-{dtype}"] == "OK"
