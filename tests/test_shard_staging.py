"""Per-shard staging of a resident L side split over four devices
(DESIGN.md §3): each device writes its own shard's kernel layout from the
rows it holds, the band step maps local rows to global ids through each
shard's first row, and the candidate set equals the numpy engine's.

Four devices need their own process (the device count is fixed when JAX
starts): ``shard_staging_cases.py`` runs every case once and prints one
JSON object.
"""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
CHIPS, ROWS_SHARD = 4, 300


@pytest.fixture(scope="module")
def cases():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    env["PYTHONPATH"] = os.path.join(ROOT, "src") + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    done = subprocess.run(
        [sys.executable, os.path.join(HERE, "shard_staging_cases.py")],
        env=env, capture_output=True, text=True, timeout=600, cwd=ROOT)
    assert done.returncode == 0, done.stderr[-4000:]
    return json.loads(done.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("name", ["interpret", "jnp"])
def test_resident_shards_staged_in_place(cases, name):
    """The kernel in interpret mode and the jnp reference: same candidates
    as the numpy engine, bit for bit, with each L shard built on its own
    device from its own rows, no byte moved and no whole L on one
    device."""
    c = cases[name]
    assert c["equal"]
    assert c["candidates"] > 0 and min(c["per_shard"]) > 0
    assert c["row_offsets"] == [s * ROWS_SHARD for s in range(CHIPS)]
    assert c["row_counts"] == [ROWS_SHARD] * CHIPS
    # 300 rows a shard, padded to 384 on its device (tl = 128)
    rows = 384
    assert c["emb_l_shape"][1] == CHIPS * rows
    assert c["shards"] == [[d, d * rows, (d + 1) * rows,
                            [2, rows, c["emb_l_shape"][2]], [d]]
                           for d in range(CHIPS)]
    span = c["span"]
    assert span["bytes_reshard"] == 0 and span["bytes_h2d"] == 0
    assert span["shards"] == CHIPS and not span["pack_hit"]
    # one device holds one L shard and the whole (replicated) R stack
    assert span["bytes_staged_device"] == c["l_shard_bytes"] + \
        c["emb_r_bytes"]
    assert span["bytes_staged"] == CHIPS * c["l_shard_bytes"] + \
        c["emb_r_bytes"]
    assert c["collectives"] == []
    assert c["whole_l_on_one"] == []
    assert c["pack_hit"]                 # memoized on the resident arrays


@pytest.mark.parametrize("name", ["interpret", "jnp", "uneven"])
def test_next_query_stages_only_its_batch(cases, name):
    """A second query with a new R over the same four L shards reuses L's
    staged layout, and any reshard that laid L out, and stages only R:
    each device builds the whole R band stack and no byte moves.  Its
    pairs equal the first query's over the same R rows."""
    c = cases[name]
    span = c["span_again"]
    assert span["l_hit"] and not span["r_hit"] and not span["pack_hit"]
    assert span["shards"] == 0
    assert span["bytes_staged_device"] == span["bytes_staged"] == \
        c["emb_r_bytes"]
    assert span["bytes_reshard"] == span["bytes_h2d"] == 0
    assert c["again_equal"]


def test_uneven_shards_from_one_device(cases):
    """1,198 rows a store holds on one device: put onto the mesh once (the
    only bytes moved), then staged as shards of 300, 300, 300 and 298 real
    rows, the last one's padded rows marked missing."""
    c = cases["uneven"]
    assert c["equal"] and c["candidates"] > 0
    assert c["row_offsets"] == [0, 300, 600, 900]
    assert c["row_counts"] == [300, 300, 300, 298]
    assert c["span"]["bytes_reshard"] == c["reshard"] > 0
    assert c["span"]["shards"] == CHIPS
    # the store's own two embed planes, and no other whole L
    assert c["whole_l_on_one"] == [[1198, 64], [1198, 64]]
