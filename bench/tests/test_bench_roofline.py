"""The work of a scan counted from its geometry, and its least time."""
import json
import os

import pytest

import benchtiny  # noqa: F401  (puts the checkout on sys.path)
from bench import roofline

CONFIGS = os.path.join(benchtiny.ROOT, "bench", "configs")


def geometry(name):
    with open(os.path.join(CONFIGS, name + ".json")) as f:
        return json.load(f)["geometry"]


def test_ops_per_update_is_the_alg4_count():
    assert sum(roofline.OPS_PER_UPDATE.values()) == 14.5


# The scan `default_geometry(512, n_proj=720)`: 720 views of 768^2.
CLINICAL = {"n_proj": 720, "n_u": 768, "n_v": 768, "n_x": 512, "n_y": 512,
            "n_z": 512}
HIGH_RES = {"n_proj": 360, "n_u": 1536, "n_v": 1536, "n_x": 1024,
            "n_y": 1024, "n_z": 1024}


@pytest.mark.parametrize("geom, updates, projection_bytes, volume_bytes", [
    (geometry("rabbitct512"), 512**3 * 496, 496 * 960 * 1248 * 4,
     512**3 * 4),
    (CLINICAL, 512**3 * 720, 720 * 768 * 768 * 4, 512**3 * 4),
    (HIGH_RES, 1024**3 * 360, 360 * 1536 * 1536 * 4, 1024**3 * 4),
])
def test_work_from_geometry(geom, updates, projection_bytes, volume_bytes):
    assert roofline.updates(geom) == updates
    assert roofline.operations(geom) == updates * 14.5
    assert roofline.compulsory_bytes(geom, 4) == projection_bytes + volume_bytes


def test_cbct512_is_compute_bound_on_one_v5e():
    geom = CLINICAL
    peak = roofline.peaks("TPU v5 lite")
    seconds, bound = roofline.least_time(geom, 4, peak)
    assert bound == "compute"
    assert seconds == pytest.approx(512**3 * 720 * 14.5 / 197e12)
    memory = (720 * 768**2 * 4 + 512**3 * 4) / 819e9
    assert memory < seconds


def test_rabbitct512_is_compute_bound_on_one_v5e():
    seconds, bound = roofline.least_time(geometry("rabbitct512"), 4,
                                         roofline.peaks("TPU v5 lite"))
    assert bound == "compute"
    assert seconds == pytest.approx(512**3 * 496 * 14.5 / 197e12)
    assert (496 * 960 * 1248 * 4 + 512**3 * 4) / 819e9 < seconds


def test_least_time_divides_over_chips():
    geom = HIGH_RES
    peak = roofline.peaks("TPU v5 lite")
    one, _ = roofline.least_time(geom, 4, peak, 1)
    four, _ = roofline.least_time(geom, 4, peak, 4)
    assert four == pytest.approx(one / 4)


def test_memory_binds_when_the_chip_computes_fast():
    geom = CLINICAL
    fast = {"flops_per_s": 1e18, "bytes_per_s": 819e9}
    seconds, bound = roofline.least_time(geom, 1, fast)
    assert bound == "memory"
    assert seconds == pytest.approx((720 * 768**2 + 512**3 * 4) / 819e9)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="no peaks"):
        roofline.peaks("TPU v99")
