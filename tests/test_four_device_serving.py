"""Serving on a four-device "chips" mesh through the normal path
(``ReadoutServer(backend="kernel")`` -> ``FusedFrontend``), on four virtual
CPU devices in a subprocess (this process keeps its one device):

  * 8 modules' answers equal the host backend's and a 1-device mesh's,
    bit for bit, over dispatches of even and uneven module counts;
  * ``report()["devices"]`` names 4 devices, and ``shards`` gives each
    2 modules, the real events of exactly those modules, 2 x B placed
    rows per dispatch and their bytes;
  * ``place_frames`` is timed once inside every ``launch_fused``;
  * a dispatch moves only its batch: the stack and the encode plan sit on
    the devices that serve their chips, so no device-to-device copy runs;
  * a mesh rebind starts the ledger over for the new device count.
"""
import json
import os
import subprocess
import sys

import pytest

from repro.data.smartpixel import N_T, N_X, N_Y

# events per module in each dispatch: even, then uneven with an empty
# module (B = 128, then 256 rows per module)
COUNTS = [[128] * 8, [200, 3, 0, 256, 17, 90, 1, 64]]
ROW_BYTES = N_T * N_Y * N_X * 4 + 4 + 1     # frames f32, y0 f32, valid

_SCRIPT = r"""
import json, os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax
import numpy as np
from jax.sharding import Mesh

from repro.core.bdt import GradientBoostedClassifier
from repro.core.readout import ReadoutChip
from repro.data.smartpixel import SmartPixelConfig, generate, train_test_split
from repro.launch.readout_server import ReadoutServer, ServerConfig

COUNTS = json.loads(sys.argv[1])
d = generate(SmartPixelConfig(n_events=6_000, seed=13))
tr, _ = train_test_split(d)
classifiers = []
for depth, leaves in ((3, 5), (4, 8), (2, 3), (4, 6)):
    clf = GradientBoostedClassifier(
        n_estimators=1, max_depth=depth, max_leaf_nodes=leaves,
        min_samples_leaf=200).fit(tr["features"], tr["label"])
    chip = ReadoutChip.build(clf)
    chip.calibrate(tr["features"], tr["label"], target_sig_eff=0.95)
    classifiers.append(chip)
chips = [classifiers[m % 4] for m in range(8)]
pool = generate(SmartPixelConfig(n_events=512, seed=3), return_frames=True)
frames, y0 = pool["frames"], pool["features"][:, 13]


def serve(srv):
    rng = np.random.default_rng(0)
    got = []
    for counts in COUNTS:
        for m, n in enumerate(counts):
            if n:
                idx = rng.integers(0, len(y0), n)
                srv.submit_frames(m, frames[idx], y0[idx])
        got.extend(srv.flush())     # one dispatch of everything queued
    return sorted([r.seq, r.chip, r.score_raw, r.keep] for r in got)


def server(backend):
    return ReadoutServer(chips, ServerConfig(
        backend=backend, max_batch=10 ** 5, max_latency_s=1e9))


out = {"host": serve(server("host"))}
four = server("kernel")
with jax.transfer_guard_device_to_device("disallow"):
    out["four"] = serve(four)
rep = four.report()
out["devices"] = rep["devices"]
out["shards"] = rep["shards"]
out["stages"] = {k: rep["stages"][k] for k in ("place_frames", "launch_fused")}
four.reset_latency_metrics()
out["shards_after_reset"] = four.report()["shards"]
one = server("kernel")
one.rebind_mesh(Mesh(np.asarray(jax.devices()[:1]), ("chips",)))
out["one"] = serve(one)
out["one_shards"] = one.report()["shards"]
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def served():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..", "src"))
    r = subprocess.run([sys.executable, "-c", _SCRIPT, json.dumps(COUNTS)],
                       env=env, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_four_device_answers_equal_host_and_one_device_mesh(served):
    assert len(served["four"]) == sum(map(sum, COUNTS))
    assert served["four"] == served["host"]
    assert served["four"] == served["one"]


def test_report_names_four_devices_with_two_modules_each(served):
    assert sorted(served["devices"]) == [0, 1, 2, 3]
    sh = served["shards"]
    assert (sh["devices"], sh["modules_per_device"]) == (4, 2)


def test_shards_count_each_devices_events_rows_and_bytes(served):
    sh = served["shards"]
    assert sh["dispatches"] == len(COUNTS)
    # device k serves modules 2k and 2k + 1
    want = [sum(c[2 * k] + c[2 * k + 1] for c in COUNTS) for k in range(4)]
    assert sh["events_per_device"] == want
    assert sum(sh["events_per_device"]) == sum(map(sum, COUNTS))
    rows = sum(2 * max(c) for c in COUNTS)         # 2 x B per dispatch
    assert sh["rows_per_device"] == [rows] * 4
    assert sh["bytes_per_device"] == [rows * ROW_BYTES] * 4
    assert served["shards_after_reset"] == dict(
        sh, dispatches=0, rows_per_device=[0] * 4,
        events_per_device=[0] * 4, bytes_per_device=[0] * 4)


def test_place_frames_is_timed_inside_every_launch(served):
    place, launch = served["stages"]["place_frames"], \
        served["stages"]["launch_fused"]
    assert place["calls"] == launch["calls"] == len(COUNTS)
    assert 0 < place["seconds"] <= launch["seconds"]


def test_rebind_starts_the_ledger_over_for_one_device(served):
    sh = served["one_shards"]
    assert (sh["devices"], sh["modules_per_device"]) == (1, 8)
    assert sh["events_per_device"] == [sum(map(sum, COUNTS))]
    assert sh["rows_per_device"] == [sum(8 * max(c) for c in COUNTS)]
