"""The traffic generator and the request keys drawn from the seed."""

import numpy as np

from chipbench import model, run, traffic


class _NoEngine:
    pass


def test_pooled_keys_are_one_set_in_a_seeded_order():
    tr = {"pooled_keys": 4, "clients": 6}
    a = run.Harness(_NoEngine(), {"K": 10}, tr, 5).keys(range(6))
    b = run.Harness(_NoEngine(), {"K": 10}, tr, 2**31 + 9).keys(range(6))
    assert sorted(map(tuple, a[:4].tolist())) == \
        sorted(map(tuple, b[:4].tolist()))
    assert a[:4].tolist() != b[:4].tolist()  # another order
    assert a[4:].tolist() != b[4:].tolist()  # drawn from the seed


def test_keys_do_not_depend_on_the_batch_they_are_drawn_in():
    # a batch is padded to the clients' number, 8 here, or past it to a
    # power of two, and the padding dropped
    seed = 2**31 + 9
    d = run.Harness(_NoEngine(), {"K": 10}, {"pooled_keys": 4, "clients": 5},
                    seed)
    whole = d.keys(range(9))
    for lo, hi in ((0, 1), (1, 4), (3, 8), (5, 9)):
        assert d.keys(range(lo, hi)).tolist() == whole[lo:hi].tolist()
    assert whole[6].tolist() == np.asarray(model.request_key(seed, 6)).tolist()


def test_open_schedule_offers_the_same_rate_for_every_seed():
    spec = {"loop": "open", "clients": 40, "period_s": 0.8}
    n = {len(traffic.open_schedule(spec, s, 10.0)) for s in (1, 2**31 + 5)}
    assert n == {500}
    due = traffic.open_schedule(spec, 3, 10.0)
    assert np.all(np.diff(due) >= 0) and due[-1] < 10.0
