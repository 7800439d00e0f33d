"""Real-TPU test tier.

Unlike tests/ (whose conftest pins XLA:CPU so the suite is hermetic), this
directory runs against whatever accelerator JAX finds: on the builder's chip
tool, in the same call as chip_smoke.py and in a process of its own (`make
chip-smoke`). Every test is marked `tpu` and SKIPS itself when the backend is
CPU, so:

    python -m pytest tests_tpu -m tpu -q       # on a TPU host: runs
    python -m pytest tests_tpu -q              # CPU-only host: all skipped

These tests exist because the hermetic suite validates XLA:CPU behavior only —
MXU matmul numerics (bf16 default input precision!), Mosaic compilation limits
and device memory behave differently on real hardware; round 4 shipped a
quantization bug (one-hot matmul float planes at default precision) that only
a real chip could reveal.
"""

from __future__ import annotations

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def pytest_configure(config):
    config.addinivalue_line("markers", "tpu: requires a real TPU backend")


@pytest.fixture(scope="session")
def tpu_backend():
    import jax

    if jax.default_backend() in ("cpu",):
        pytest.skip("no TPU backend (CPU platform)")
    return jax.default_backend()


@pytest.fixture(scope="session", autouse=True)
def _compile_cache_warmup():
    """Pre-compile the shared device-stage shapes once per session.

    utils/jax_setup.py already points jax_compilation_cache_dir at a
    persistent directory, but without a warmup pass every test still pays its
    own cold XLA compile. This fixture runs one tiny query per SHARED program
    family — ungrouped filter-agg, dictionary-keyed grouped agg, f64 grouped extremes, and the
    gather-join agg — at the 512-row bucket every small test lands in, so the
    in-process jit caches and the on-disk XLA cache are warm before the first
    test; a session rerun then costs seconds, not minutes. Per-test compiles
    for exotic shapes still happen lazily. A warm-up that fails fails the
    session: it runs the same programs the tests do.
    """
    import jax

    if jax.default_backend() in ("cpu",):
        yield  # hermetic/cpu invocation: nothing to warm, tests skip anyway
        return
    import numpy as np

    import daft_tpu
    from daft_tpu import col
    from daft_tpu.config import execution_config_ctx

    rng = np.random.default_rng(0)
    n = 400  # < 512 bucket, the floor every small equivalence test uses
    fact = daft_tpu.from_pydict({
        "k": [int(x) for x in rng.integers(0, 7, n)],
        "s": [f"g{i % 5}" for i in range(n)],
        "v": rng.uniform(0, 10, n).tolist(),
        "q": [int(x) for x in rng.integers(1, 9, n)],
    }).collect()
    dim = daft_tpu.from_pydict({
        "d_k": list(range(7)),
        "d_g": [f"d{i % 3}" for i in range(7)],
    }).collect()
    with execution_config_ctx(device_mode="on"):
        # ungrouped filter-agg (mm planes + int bit-slice sum)
        fact.where(col("v") > 1.0).agg(
            col("v").sum().alias("sv"), col("q").sum().alias("sq"),
            col("v").count().alias("c")).to_pydict()
        # dict-keyed grouped agg (one-hot matmul program)
        fact.groupby("s").agg(col("v").sum().alias("sv"),
                              col("q").count().alias("c")).to_pydict()
        # f64 grouped extremes (exact min/max program variant)
        fact.groupby("k").agg(col("v").min().alias("lo"),
                              col("v").max().alias("hi")).to_pydict()
        # gather-join + grouped agg (index planes + packed dim matrix)
        (fact.join(dim, left_on="k", right_on="d_k")
         .groupby("d_g").agg(col("v").sum().alias("sv"))).to_pydict()
    yield
