"""The traffic generator: seeded, and the copied memory traces equal the
program's own generators."""
import numpy as np
import pytest

from .. import traffic

BIG = 2 ** 40 + 17       # seeds may exceed 32 bits
CFG = {"n_cores": 8, "length": 96, "n_data": 8, "n_rows": 64,
       "write_frac": 0.3}
MIX = {"traces": list(traffic.TRACE_KINDS), "seeds_per_call": 2}


@pytest.mark.parametrize("kind", sorted(traffic.TRACE_KINDS))
def test_memory_traces_equal_the_programs_generators(kind):
    from repro.sim.trace import TRACES, TraceSpec

    for seed in (0, 12345):
        want = TRACES[kind](TraceSpec(n_cores=8, length=96, n_banks=8,
                                      n_rows=64, write_frac=0.3, seed=seed))
        got = traffic.memory_trace(kind, seed, n_cores=8, length=96,
                                   n_banks=8, n_rows=64, write_frac=0.3)
        for field, arr in got.items():
            np.testing.assert_array_equal(arr, np.asarray(getattr(want,
                                                                  field)))


def test_a_memsys_call_is_determined_by_the_seed():
    a = traffic.memsys_call(MIX, CFG, BIG, 0)
    b = traffic.memsys_call(MIX, CFG, BIG, 0)
    c = traffic.memsys_call(MIX, CFG, BIG, 1)
    assert [p["seed"] for p in a] == [p["seed"] for p in b]
    assert [p["kind"] for p in a] == [k for k in MIX["traces"]
                                      for _ in range(2)]
    assert all(np.array_equal(p["trace"]["row"], q["trace"]["row"])
               for p, q in zip(a, b))
    assert {p["seed"] for p in a}.isdisjoint({p["seed"] for p in c})
