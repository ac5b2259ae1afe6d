"""The token feed is a function of the seed and the step alone."""
import numpy as np

from bench import harness
from bench.traffic.tokens import TokenFeed


def test_token_feed_is_a_function_of_seed_and_step():
    a = TokenFeed(harness.seed_key(5, 2), 256, 2, 16)
    b = TokenFeed(harness.seed_key(5, 2), 256, 2, 16)
    c = TokenFeed(harness.seed_key(6, 2), 256, 2, 16)
    assert np.array_equal(a.batch(3)["tokens"], b.batch(3)["tokens"])
    assert not np.array_equal(a.batch(3)["tokens"], a.batch(4)["tokens"])
    assert not np.array_equal(a.batch(3)["tokens"], c.batch(3)["tokens"])
    x = a.batch(0)
    assert np.array_equal(x["tokens"][:, 1:], x["labels"][:, :-1])
