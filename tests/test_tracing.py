"""``repro.tracing``: host fetches counted per array, and device programs
named for the trace."""
import jax
import jax.numpy as jnp
import numpy as np

from repro import tracing


def test_to_host_counts_one_fetch_per_array():
    c = tracing.EngineCounters()
    one = tracing.to_host(jnp.arange(3), "answer", c)
    assert isinstance(one, np.ndarray) and one.tolist() == [0, 1, 2]
    assert c.host_fetches == 1
    many = tracing.to_host([jnp.zeros(2), jnp.ones((2, 2)), jnp.int32(4)],
                           "temporal_state", c)
    assert [a.shape for a in many] == [(2,), (2, 2), ()]
    assert c.host_fetches == 4
    assert [a.tolist() for a in tracing.to_host((jnp.ones(1),), "answer")] \
        == [[1.0]]


def test_named_program_is_an_identifier():
    fn = tracing.named(lambda x, y=1: x + y, "plan_region@r0")
    assert fn.__name__ == "plan_region_r0"
    assert fn(2, y=3) == 5
    text = jax.jit(fn).lower(jnp.float32(1)).as_text()
    assert "@jit_plan_region_r0" in text
