"""``correct`` fails where it must: the control and the planted faults.

The control is the reference put in the program's place at one precision
below the configuration's (``high`` for float32 at ``highest``); each
fault breaks the timed path underneath an otherwise whole run: an answer
altered where stage 1 or the index produces it, half of each batch
answered from the other half, and, on four (virtual) devices, the
exchange of per-shard results between chips left out. Each run skips the
look for a chip and is cut to a size the CPU holds.
"""
from __future__ import annotations

import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(Path(__file__).resolve().parent))

from test_bench_harness import cpu_run, tiny_cell  # noqa: E402

from bench import control  # noqa: E402


def test_program_is_correct_and_control_is_not():
    cell = tiny_cell(traffic="sift1m.closed64-k10")
    assert cpu_run(cell)["correct"] is True
    res = cpu_run(cell, service=control.control_service("high"))
    assert res["correct"] is False
    assert res["checks"]["score_err"]["value"] > \
        res["checks"]["score_err"]["limit"]


@pytest.mark.parametrize("fault", ["stage1", "altered", "half_batch"])
def test_a_planted_fault_makes_the_run_incorrect(fault):
    cell = tiny_cell(traffic="sift1m.closed64-k10")
    res = cpu_run(cell, service=control.fault_service(fault))
    assert res["correct"] is False
    assert res["failed"] == 0  # answered, but wrong


SHARDED = r"""
import sys
sys.path[:0] = [{root!r}, {root!r} + "/src", {tests!r}]
import jax, jax.numpy as jnp
from test_bench_harness import cpu_run, tiny_cell
from bench import control

cell = tiny_cell("cohere768-10m-shard4-flat", "cohere768-shard4.open-k100",
                 k=20)
cell["config"]["rows"] = 4096
print("sound", cpu_run(cell)["correct"])
print("control", cpu_run(cell, service=control.control_service("high"))
      ["correct"])
gather = jax.lax.all_gather

def local_only(x, axes, axis=0, tiled=False):
    # the exchange between chips left out: every other shard's slot is
    # empty (score -inf, id -1), so each chip merges its own rows alone
    full = gather(x, axes, axis=axis, tiled=tiled)
    fill = -jnp.inf if jnp.issubdtype(x.dtype, jnp.floating) else -1
    keep = jnp.arange(full.shape[axis]) < x.shape[axis]
    shape = [1] * full.ndim
    shape[axis] = full.shape[axis]
    mine = jnp.concatenate([x] * (full.shape[axis] // x.shape[axis]), axis)
    return jnp.where(keep.reshape(shape), mine, fill)

jax.lax.all_gather = local_only
print("no_exchange", cpu_run(cell, seed=2 ** 31 + 4)["correct"])
"""


def test_sharded_cell_control_and_missing_exchange_fail():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    script = SHARDED.format(root=str(ROOT),
                            tests=str(Path(__file__).resolve().parent))
    r = subprocess.run([sys.executable, "-c", script], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    lines = dict(line.split() for line in r.stdout.strip().splitlines())
    assert lines == {"sound": "True", "control": "False",
                     "no_exchange": "False"}
