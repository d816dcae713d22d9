"""The overwrite reference, worked by hand, and the rbd_bench check
catching a planted splice fault (CPU, small deployment)."""
import contextlib
import copy
import dataclasses

import numpy as np

from benchmark import harness
from benchmark.reference import gf256_rs, rbd_overwrite

K, M, SU = 4, 2, 4096
CELL = "rbd_ec42.randwrite_4k"


def test_one_overwrite_changes_one_data_chunk_and_both_parities():
    """A 4 KiB overwrite of chunk 2 of stripe 1 of a two-stripe object:
    data shard 2 and both coding shards change in stripe 1's range and
    nowhere else; coding row 0 is the XOR of the data chunks and row 1
    weighs chunk j by 2^j, so the coding deltas are d and 4 * d in
    GF(2^8), d = old ^ new."""
    rng = np.random.default_rng(4)
    sw = K * SU
    old = rng.bytes(2 * sw)
    new = rng.bytes(SU)
    at = sw + 2 * SU
    (body, shards), = rbd_overwrite.expected(
        lambda _n: old, [(0, at, new)], [0], K, M, SU).values()
    assert body == old[:at] + new + old[at + SU:]
    before = gf256_rs.all_shards(old, K, M, SU)
    changed = {(j, s) for j in range(K + M) for s in range(2)
               if not np.array_equal(before[j, s * SU:(s + 1) * SU],
                                     shards[j, s * SU:(s + 1) * SU])}
    assert changed == {(2, 1), (4, 1), (5, 1)}
    d = np.frombuffer(old[at:at + SU], np.uint8) ^ \
        np.frombuffer(new, np.uint8)
    assert np.array_equal(shards[2, SU:], np.frombuffer(new, np.uint8))
    assert np.array_equal(shards[4, SU:] ^ before[4, SU:], d)
    assert np.array_equal(shards[5, SU:] ^ before[5, SU:],
                          gf256_rs.MUL[4][d])
    # and the reference is a full re-encode of the spliced body
    assert np.array_equal(shards, gf256_rs.all_shards(body, K, M, SU))


def test_writes_apply_in_the_order_given():
    one, two = b"\x01" * 8, b"\x02" * 8
    got = rbd_overwrite.bodies_after(
        lambda n: bytes(32), [(0, 4, one), (1, 0, one), (0, 8, two)], [0])
    assert got == {0: bytes(4) + b"\x01" * 4 + two + bytes(16)}


@contextlib.contextmanager
def splice_misplaced():
    """Every shard splices a partial write one chunk away from where
    the primary put it."""
    from ceph_tpu.osd.ec_backend import ECBackend
    orig = ECBackend.handle_sub_write

    def handle_sub_write(self, msg, store, pg=None):
        if msg.partial:
            msg = dataclasses.replace(msg, offset=msg.offset ^ SU)
        return orig(self, msg, store, pg)

    ECBackend.handle_sub_write = handle_sub_write
    try:
        yield
    finally:
        ECBackend.handle_sub_write = orig


def _small_bench(seed: int):
    cell = harness.Cell(CELL)
    cfg = copy.deepcopy(cell.config)
    cfg["cluster"]["n_osds"] = 8
    cfg["metadata_pool"]["pg_num"] = 8
    cfg["data_pool"]["pg_num"] = 16
    cfg["image"]["order"] = 16
    cfg["image"]["size_bytes"] = 32 << 16
    return cell.driver.build(cfg, cell.traffic, seed,
                             harness.span_factory(False), harness.log)


def _window(bench):
    res = bench.window(1.0)
    checks = bench.check()
    return res, checks


def test_sound_window_passes_and_a_misplaced_splice_fails():
    bench = _small_bench(2**31 + 25)
    res, checks = _window(bench)
    assert res["attempted"] > 16
    assert all(c["value"] == 0 for c in checks.values()), checks
    assert res["layer"]["codec_min_bytes"] == \
        len(bench.client.done) * (K + M) * SU
    with splice_misplaced():
        res, checks = _window(bench)
    assert res["failed"] == 0
    assert checks["shards_differing"]["value"] > 0, checks
    assert checks["bytes_read_back_differing"]["value"] > 0, checks
