"""The generator: the seed decides the world, and nothing else does."""

from benchmark import worlds

DENEB = {"fork": "deneb", "preset": "mainnet", "validators": 1 << 12}
EDGE = {"kind": "epoch_edge", "epoch": 1, "miss_share": [0.01, 0.03]}


def root(state) -> bytes:
    return type(state).hash_tree_root(state)


def test_epoch_edge_follows_the_seed():
    big = (1 << 31) + 12345  # the driver's seeds do not fit 32 signed bits
    a = worlds.build(DENEB, EDGE, big)
    b = worlds.build(DENEB, EDGE, big)
    c = worlds.build(DENEB, EDGE, big + 1)
    assert root(a.pre) == root(b.pre) != root(c.pre)
    assert int(a.pre.slot) == 63 and a.target_slot == 64
    for shares in a.miss_shares.values():
        assert all(0.01 <= share <= 0.03 for share in shares)
    flags = list(a.pre.previous_epoch_participation)
    missing = sum(1 for f in flags if f != 0b111) / len(flags)
    assert 0.02 < missing < 0.1
    assert flags != list(a.pre.current_epoch_participation)
