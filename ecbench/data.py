"""Seeded volumes: which bodies a volume holds, and writing them to disk.

A configuration fixes the body sizes AND their order in the volume
(`needles.layout_seed`); the run's seed only fills them. So every seed
gives the same offsets, the same needles on a lost shard with the same
extents there, and the same work: with the order drawn from the run's
seed the mean extent on the lost shard swung by 4 % either way and
GETs per second with it (my chip run, PR 24). Copied from
chip_smoke.py's `needle_plan`/`fabricate_volume` and changed in that
one respect.
"""

from __future__ import annotations

import dataclasses

import numpy as np

MIB = 1 << 20
GIB = 1 << 30


@dataclasses.dataclass
class SeededVolume:
    """One fabricated volume: where its files are and what is in it."""

    vid: int
    base: str  # <data_dir>/<vid>, without extension
    cookie: int
    sizes: list[int]  # body size of needle i (needle id i + 1)
    starts: list[int]  # offset of body i in `blob`
    offsets: list[int]  # byte offset of record i in the .dat
    dat_bytes: int
    blob: np.ndarray  # uint8: all bodies, back to back

    def fid(self, i: int) -> str:
        return f"{self.vid},{i + 1:x}{self.cookie:08x}"

    def body(self, i: int) -> memoryview:
        s = self.starts[i]
        return memoryview(self.blob[s : s + self.sizes[i]])

    def record_extent(self, i: int) -> tuple[int, int]:
        """[lo, hi) of record i in the .dat, its padding included."""
        hi = self.offsets[i + 1] if i + 1 < len(self.offsets) else self.dat_bytes
        return self.offsets[i], hi


def needle_plan(vid: int, volume_bytes: int, plan: dict) -> list[int]:
    """Body sizes of one volume, in the order they are written:
    `volume_bytes` of large bodies and, per GiB, `small_per_gib` small
    ones whose sizes are spread evenly over [small_min, small_max],
    shuffled by the configuration's `layout_seed`."""
    large = int(plan["large_body_bytes"])
    n_small = max(8, int(plan["small_per_gib"]) * volume_bytes // GIB)
    sizes = [large] * max(volume_bytes // large, 1)
    sizes += [
        int(s)
        for s in np.linspace(
            int(plan["small_min_bytes"]), int(plan["small_max_bytes"]), n_small
        )
    ]
    np.random.default_rng([int(plan["layout_seed"]), vid, 0xB0D1E5]).shuffle(sizes)
    return sizes


def fabricate_volume(
    directory: str, vid: int, seed: int, volume_bytes: int, plan: dict
) -> SeededVolume:
    """Write a sealed volume through the program's storage layer (not
    over HTTP: the multipart parse took 45 s a GiB, PR 21). Bodies come
    from one generator call."""
    from seaweedfs_tpu.storage.needle import Needle
    from seaweedfs_tpu.storage.volume import Volume

    sizes = needle_plan(vid, volume_bytes, plan)
    total = sum(sizes)
    # raw 64-bit words: ten times faster than Generator.bytes
    words = np.random.default_rng([seed, vid, 0xDA7A]).bit_generator.random_raw(
        -(-total // 8)
    )
    blob = words.view(np.uint8)[:total]
    starts = np.concatenate([[0], np.cumsum(sizes)[:-1]]).tolist()
    cookie = 0x5EA0000 + vid
    vol = Volume(directory, vid, needle_map_kind="memory")
    offsets = []
    for i, (s, size) in enumerate(zip(starts, sizes)):
        off, _ = vol.write_needle(
            Needle(cookie=cookie, needle_id=i + 1, data=blob[s : s + size].tobytes())
        )
        offsets.append(off)
    vol.flush()
    base = vol.base_file_name(directory, "", vid)
    dat_bytes = int(vol.size)
    vol.close()
    return SeededVolume(vid, base, cookie, sizes, starts, offsets, dat_bytes, blob)
