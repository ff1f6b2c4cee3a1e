"""The four-chip driver (``drivers/train_mesh.py``) through ``run.run_cell``
at a small size, on four virtual CPU devices in a process of its own (the
device count is fixed when jax starts): ``correct`` comes out true, the
memory reported is the fullest device's, the step's work is the whole
job's over the device count, and a program that keeps the scores whole on
one device is stopped before its first tree.

    python3 -m pytest benchmarks/tests -q
"""
import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(os.path.dirname(HERE))

CHILD = r"""
import json, sys
sys.path.insert(0, {here!r})
from small import NO_CHIP, small_cell
from benchmarks import run as bench_run
from benchmarks.drivers import train_mesh
cell = small_cell("higgs-42m-data", "train-mesh", rows=20000)
cell.update(name="higgs-42m-data.train", chips=4)
outs = []
real = train_mesh.train.run
def spy(*a, **kw):
    outs.append(real(*a, **kw))
    return outs[-1]
train_mesh.train.run = spy
if {unchanged!r}:
    from lightgbm_tpu import boosting
    import jax
    init = boosting.GBDT._setup_device_inner
    def whole(self, train):
        init(self, train)
        self.scores = jax.device_put(self.scores, jax.devices()[0])
    boosting.GBDT._setup_device_inner = whole
res = bench_run.run_cell(cell, 2 ** 31 + 5, 1.0, False, NO_CHIP,
                         trace_dir={trace!r})
ctx = outs[-1]["context"]
print(json.dumps({{"result": res, "devices": ctx["devices"],
                  "peaks": ctx["device_peaks"], "work": ctx["work"]}}))
"""


def child(tmp_path, unchanged=False):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = CHILD.format(here=HERE, unchanged=unchanged,
                        trace=str(tmp_path / "trace"))
    return subprocess.run([sys.executable, "-c", code], cwd=CHECKOUT,
                          env=env, capture_output=True, text=True,
                          timeout=900)


def test_mesh_driver_on_four_devices(tmp_path):
    proc = child(tmp_path)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    res = out["result"]
    assert res["correct"], res["compared"]
    assert out["devices"] == 4 and len(out["peaks"]) == 4
    # the CPU reports no memory: the fullest device's reading is 0 too
    assert res["device"]["memory_peak_bytes"] == max(out["peaks"])
    hist, part, step = (out["work"][k] for k in
                        ("histogram", "partition", "step"))
    # the step's work is the whole job's (histograms, row movement, one
    # pass over 20,000 rows' scores) over the four devices
    rows = 20000
    for key, per_row in (("bytes", 28), ("ops", 8)):
        trees = res["attempted"]
        whole = hist[key] + part[key] + trees * rows * per_row
        assert abs(step[key] * 4 - whole) <= 1e-6 * whole, (key, step, whole)


def test_scores_whole_on_one_device_end_the_run(tmp_path):
    proc = child(tmp_path, unchanged=True)
    assert proc.returncode != 0
    assert "cannot run the cell" in proc.stderr, proc.stderr[-3000:]
