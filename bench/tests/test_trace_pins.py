"""The per-layer metrics read from the TPU trace committed under
``data/tpu_v5e_fl_round``, pinned to the values the reduction gave when
the program's simulator spans (``sim/*``) were added.  A later change to
the join may add spans and metrics; it must not move these."""
import glob
import gzip
import json
import os
import shutil

import pytest

from benchkit import readers
from benchkit import trace_reduce as tr

CHIP = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "tpu_v5e_fl_round")

#: shape-only work counts for the readers that need them (made up, fixed)
WORK = {"flops_per_round": 1.0e9, "wire_hbm_bytes": 4.0e6,
        "wire_ici_bytes": 0.0, "fleet_bytes": 3.2e7}

PINNED = {
    "coverage": 0.884630944261032,
    "provenanced": 0.9583842108813103,
    "busy_s": 0.006478791,
    "window_s": 0.051010659,
    "local_steps_ms": 0.10673204545454122,
    "wire_ms": 0.023525659090909062,
    "wire_roofline": 20.760331794029067,
    "step_mfu": 0.4378501634474211,
    "idle_share": 87.2991427144668,
}


@pytest.fixture(scope="module")
def ctx(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("chip_trace")
    meta = json.load(open(os.path.join(CHIP, "meta.json")))
    with gzip.open(os.path.join(CHIP, "trace.xplane.pb.gz")) as src, \
            open(tmp / "trace.xplane.pb", "wb") as dst:
        shutil.copyfileobj(src, dst)
    hlo = {}
    for p in glob.glob(os.path.join(CHIP, "*.hlo.txt.gz")):
        with gzip.open(p, "rt") as f:
            hlo[os.path.basename(p)[:-len(".hlo.txt.gz")]] = f.read()

    class Dev:
        device_kind = "TPU v5 lite"
    return tr.reduce(str(tmp), {"rounds": meta["rounds"], "hlo": hlo,
                                "work": dict(WORK)}, [Dev()])


@pytest.mark.parametrize("name", ["coverage", "provenanced", "busy_s",
                                  "window_s"])
def test_trace_totals_pinned(ctx, name):
    assert ctx[name] == pytest.approx(PINNED[name], rel=1e-12, abs=0)


@pytest.mark.parametrize("name", ["local_steps_ms", "wire_ms",
                                  "wire_roofline", "step_mfu", "idle_share"])
def test_reader_pinned(ctx, name):
    assert getattr(readers, name)(ctx) == pytest.approx(PINNED[name],
                                                        rel=1e-12, abs=0)


def test_fleet_readers_read_nothing_in_a_round_trace(ctx):
    assert readers.fleet_ms(ctx) is None
    assert readers.fleet_roofline(ctx) is None
