"""The benchmark's own tests run on the CPU at tiny sizes."""
import glob
import gzip
import json
import os
import shutil
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
for p in (os.path.join(ROOT, "src"), BENCH, HERE):
    if p not in sys.path:
        sys.path.insert(0, p)

#: a tiny FL round traced on one TPU v5e (``data/tpu_v5e_fl_round``)
CHIP = os.path.join(HERE, "data", "tpu_v5e_fl_round")

#: shape-only work counts for the readers that need them (made up, fixed)
CHIP_WORK = {"flops_per_round": 1.0e9, "wire_hbm_bytes": 4.0e6,
             "wire_ici_bytes": 0.0, "fleet_bytes": 3.2e7}


@pytest.fixture(scope="session")
def chip_ctx(tmp_path_factory):
    """The committed chip trace reduced as a traced run reduces it, with
    ``fl/local_steps`` declared as a reader's span."""
    from benchkit import trace_reduce
    tmp = tmp_path_factory.mktemp("chip_trace")
    with open(os.path.join(CHIP, "meta.json")) as f:
        meta = json.load(f)
    with gzip.open(os.path.join(CHIP, "trace.xplane.pb.gz")) as src, \
            open(tmp / "trace.xplane.pb", "wb") as dst:
        shutil.copyfileobj(src, dst)
    hlo = {}
    for p in glob.glob(os.path.join(CHIP, "*.hlo.txt.gz")):
        with gzip.open(p, "rt") as f:
            hlo[os.path.basename(p)[:-len(".hlo.txt.gz")]] = f.read()

    class Dev:
        device_kind = "TPU v5 lite"
    return meta, trace_reduce.reduce(
        str(tmp), {"rounds": meta["rounds"], "hlo": hlo,
                   "work": dict(CHIP_WORK)}, [Dev()],
        spans=("fl/local_steps",))
