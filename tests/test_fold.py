"""Fabrics with more nodes than devices: the fold's tables
(``repro.device.schedule.fold_schedule``), its counters, and broadcasts of
``dragonfly(128)``, ``ring(8)`` and ``torus2d(4, 4)`` folded onto 1, 2 and
4 emulated devices, bit-exact to the payload in every node and equal to
the plain numpy replay of the unfolded schedule (``repro.core.replay``).

The tables are checked in-process; anything that runs a broadcast spawns
a subprocess with emulated host devices, as tests/test_device.py does.
"""

import json

import numpy as np
import pytest

from repro.device.schedule import fold_schedule
from test_device import V5E_2X2, run_multidevice

FABRICS = {"dragonfly128": ("dragonfly", 128), "ring8": ("ring", 8),
           "torus4x4": ("torus2d", 16)}
# (fabric, algo, root) of each folded run: BBS from root 0, and the
# binomial baseline from root 3, whose relay rows cross the fold
RUNS = {name: (name, "bbs", 0) for name in FABRICS}
RUNS["ring8_binomial_root3"] = ("ring8", "binomial", 3)


def _executable(fabric, nbytes):
    from repro import api
    from repro.core import topology as T

    return api.compile(T.by_name(*FABRICS[fabric]),
                       "full_duplex").executable(0, nbytes)


def _holds(rel, ab, c, K, total):
    """Whether a schedule entry holds a row at cycle ``c``, straight from
    ``DeviceSchedule``'s convention."""
    if ab >= 0:
        return True
    return rel > -(10 ** 6) and 0 <= c * K + rel < total


@pytest.mark.parametrize("chips", [1, 2, 4])
@pytest.mark.parametrize("fabric", sorted(FABRICS))
def test_fold_tables_cover_every_pair_once(fabric, chips):
    """Each sub-round's pairs are the schedule's, each once: the local
    moves join nodes of one chip, each chip-level permutation is a matching
    of chips carrying the pairs of its chip pairs in stacks, and every
    entry names the node's slot and the schedule's row for it."""
    ex = _executable(fabric, 4096)
    s = ex.schedule
    fold = fold_schedule(ex.schedule, chips)
    per = s.num_devices // chips
    assert fold.nodes_per_chip == per and len(fold.rounds) == s.d

    def entries(tab, chip):
        return [(int(tab.slot[chip, i]), int(tab.rel[chip, i]),
                 int(tab.abs[chip, i])) for i in range(tab.width)]

    def row(node, side, r):
        rel = (s.send_rel if side == "send" else s.recv_rel)[r][node]
        ab = (s.send_abs if side == "send" else s.recv_abs)[r][node]
        return node % per, int(rel), int(ab)

    pad = (0, -(10 ** 6), -1)
    for r, rnd in enumerate(fold.rounds):
        seen = []
        for c in range(chips):
            src = entries(rnd.local_send, c)
            dst = entries(rnd.local_recv, c)
            pairs = [(u, v) for u, v in s.perms[r]
                     if u // per == c and v // per == c]
            assert src == [row(u, "send", r) for u, _ in pairs] \
                + [pad] * (rnd.local_send.width - len(pairs))
            assert dst == [row(v, "recv", r) for _, v in pairs] \
                + [pad] * (rnd.local_recv.width - len(pairs))
            seen += pairs
        for p in rnd.permutes:
            sources = [a for a, _ in p.pairs]
            targets = [b for _, b in p.pairs]
            assert len(set(sources)) == len(sources)
            assert len(set(targets)) == len(targets)
            assert all(a != b for a, b in p.pairs)
            for a, b in p.pairs:
                pairs = [(u, v) for u, v in p.node_pairs
                         if u // per == a and v // per == b]
                assert pairs and len(pairs) <= p.send.width
                assert entries(p.send, a)[:len(pairs)] == \
                    [row(u, "send", r) for u, _ in pairs]
                assert entries(p.recv, b)[:len(pairs)] == \
                    [row(v, "recv", r) for _, v in pairs]
            for c in set(range(chips)) - set(sources):
                assert entries(p.send, c) == [pad] * p.send.width
            seen += p.node_pairs
        assert sorted(seen) == sorted(s.perms[r])


@pytest.mark.parametrize("chips", [1, 2, 4])
@pytest.mark.parametrize("fabric", sorted(FABRICS))
def test_fold_counters_match_a_direct_count(fabric, chips):
    """Rows moved inside chips and across them, permutes and their stacked
    rows, each chip's rows sent and received, and the pairs between
    non-neighbour chips, counted pair by pair over every cycle."""
    ex = _executable(fabric, 12000)
    s, m = ex.schedule, ex.num_groups
    fold = fold_schedule(ex.schedule, chips)
    per = s.num_devices // chips
    coords = [c.coords for c in V5E_2X2[:chips]]
    got = fold.counters(m, coords)
    want = {"rows_local": 0, "rows_cross": 0}
    sent, received = [0] * chips, [0] * chips
    total = m * s.K
    for c in range(s.num_cycles(m)):
        for r, pairs in enumerate(s.perms):
            for u, v in pairs:
                if _holds(s.recv_rel[r][v], s.recv_abs[r][v], c, s.K, total):
                    received[v // per] += 1
                    kind = ("rows_local" if u // per == v // per
                            else "rows_cross")
                    want[kind] += 1
                if _holds(s.send_rel[r][u], s.send_abs[r][u], c, s.K, total):
                    sent[u // per] += 1
    assert {k: got[k] for k in want} == want
    assert got["rows_sent"] == sent and got["rows_received"] == received
    perms = sum(len(rnd.permutes) for rnd in fold.rounds)
    assert got["permutes"] == perms * s.num_cycles(m)
    assert got["permute_rows"] == s.num_cycles(m) * sum(
        p.send.width for rnd in fold.rounds for p in rnd.permutes)
    steps = lambda a, b: sum(abs(x - y) for x, y in zip(a, b))  # noqa: E731
    assert got["far_pairs"] == sum(
        1 for pairs in s.perms for u, v in pairs
        if steps(coords[u // per], coords[v // per]) > 1)
    if chips == 1:
        assert got["rows_cross"] == got["permutes"] == 0


def test_one_node_per_chip_folds_to_the_schedule():
    """With one node per chip there is nothing to hand over inside a chip,
    and each sub-round is one permutation of single rows: the schedule's
    own matching, in its order."""
    from repro import api
    from repro.core import topology as T

    ex = api.compile(T.torus2d(2, 2)).executable(0, 250 << 20)
    s = ex.schedule
    fold = fold_schedule(ex.schedule, 4)
    counts = fold.counters(ex.num_groups)
    assert counts["permutes"] == 104 == s.d * s.num_cycles(ex.num_groups)
    assert counts["rows_local"] == 0
    for r, rnd in enumerate(fold.rounds):
        assert rnd.local_send.width == 0
        (p,) = rnd.permutes
        assert p.pairs == [tuple(uv) for uv in s.perms[r]]
        assert p.send.width == 1
        assert p.send.rel[:, 0].tolist() == s.send_rel[r].tolist()
        assert p.recv.rel[:, 0].tolist() == s.recv_rel[r].tolist()


def test_fold_refuses_unequal_blocks():
    ex = _executable("ring8", 4096)
    with pytest.raises(ValueError, match="equal blocks"):
        fold_schedule(ex.schedule, 3)
    with pytest.raises(ValueError, match="equal blocks"):
        ex.mesh([object()] * 3)


def test_dragonfly_groups_sit_on_the_2x2_chips():
    """Group g of dragonfly(128) lies on chip g; the global pairs between
    chips 0 and 3 and between chips 1 and 2 join diagonal chips and are
    named, not refused."""
    from repro.core import topology as T
    from repro.device import far_pairs, node_coords

    topo = T.by_name("dragonfly", 128)
    coords = node_coords(topo, V5E_2X2)
    assert all(coords[v] == V5E_2X2[v // 32].coords for v in range(128))
    ex = _executable("dragonfly128", 64_000_000)
    pairs = [uv for perm in ex.schedule.perms for uv in perm]
    far = far_pairs(pairs, coords)
    assert far and {(u // 32, v // 32) for u, v in far} <= {
        (0, 3), (3, 0), (1, 2), (2, 1)}
    assert len(far) == fold_schedule(ex.schedule, 4).counters(
        ex.num_groups, [c.coords for c in V5E_2X2])["far_pairs"]


def test_replay_delivers_the_payload_to_every_node():
    """The numpy replay of the unfolded schedule leaves the root's packet
    rows in every node, on a multi-tree plan and on a relayed baseline."""
    from repro import api
    from repro.core import topology as T
    from repro.core.replay import replay_broadcast

    for topo, algo in ((T.by_name("dragonfly", 128), "bbs"),
                       (T.ring(8), "binomial")):
        ex = api.compile(topo).executable(0, 4096, algo=algo)
        total = ex.num_groups * ex.schedule.K
        packets = np.random.RandomState(2).rand(total, 7).astype(np.float32)
        bufs = replay_broadcast(ex.schedule, ex.num_groups, packets)
        assert bufs.shape[:2] == (topo.num_nodes,
                                  total + ex.schedule.num_relay)
        assert (bufs[:, :total] == packets[None]).all(), (topo.name, algo)


@pytest.mark.parametrize("case", sorted(RUNS))
def test_folded_broadcast_is_bit_exact_and_matches_the_replay(case):
    """``api.compile(...).executable(root, n).run(x, mesh)`` on meshes of
    1, 2 and 4 devices (and by default on all of them): every node's copy
    is the payload bit for bit and equals the numpy replay's; ``verify``
    agrees; the Pallas step refuses a device with several nodes."""
    fabric, algo, root = RUNS[case]
    name, n = FABRICS[fabric]
    out = run_multidevice(f"""
        import json
        import jax, jax.numpy as jnp, numpy as np
        from repro import api
        from repro.core import topology as T
        from repro.core.replay import replay_broadcast
        from repro.core.simconfig import DeviceConfig, SimConfig
        from repro.device.runner import _pad_packets
        topo = T.by_name({name!r}, {n})
        model = api.compile(topo, "full_duplex", server=True)
        got = {{}}
        ex = model.executable({root}, 12000, algo={algo!r})
        x = np.random.RandomState(5).rand(3000).astype(np.float32)
        total = ex.num_groups * ex.schedule.K
        packets = np.asarray(_pad_packets(jnp.asarray(x), total))
        ref = replay_broadcast(ex.schedule, ex.num_groups, packets)
        ref = ref[:, :total].reshape(topo.num_nodes, -1)[:, :x.size]
        for k in (1, 2, 4, None):
            mesh = ex.mesh(None if k is None else jax.devices()[:k])
            out = np.asarray(ex.run(jnp.asarray(x), mesh))
            got[str(k)] = {{
                "devices": mesh.devices.size,
                "shape": list(out.shape),
                "exact": bool((out.view(np.uint32)
                               == x.view(np.uint32)[None]).all()),
                "replay": bool((out == ref).all()),
                "verify": ex.verify(x, mesh).ok}}
        cfg = SimConfig(device=DeviceConfig(use_pallas=True))
        ex = model.executable({root}, 4096, algo={algo!r}, config=cfg)
        try:
            ex.run(jnp.ones(1024, jnp.float32), ex.mesh(jax.devices()[:1]))
            got["pallas"] = "ran"
        except ValueError as e:
            got["pallas"] = "refused" if "jnp step" in str(e) else str(e)
        print(json.dumps(got))
    """, devices=4)
    got = json.loads(out.strip().splitlines()[-1])
    assert got.pop("pallas") == "refused"
    for key, r in got.items():
        assert r["exact"] and r["replay"] and r["verify"], (key, r)
        assert r["shape"][0] == n
        assert r["devices"] == (4 if key == "None" else int(key))
