"""The least bytes of ``peel_roofline``, counted by hand, and the table
of chip peaks it divides by."""
import pytest

from cost import peel_least_bytes


def test_least_bytes_hand_counted():
    # K4: n_r = 6 edges, n_s = 4 triangles, C = 3.
    # incidence 12 + deg0 6 + CSR offsets 7 + CSR ids 12 + core 6 = 43
    # int32 entries
    assert peel_least_bytes(6, 4, 3) == 43 * 4


def test_least_bytes_grow_with_the_incidence():
    assert peel_least_bytes(10, 20, 3) - peel_least_bytes(10, 19, 3) == 24


def test_peaks_known_device():
    from peaks import peaks_for
    assert peaks_for("tpu", "TPU v5 lite")["hbm_bytes_per_s"] == 819e9


@pytest.mark.parametrize("platform,kind", [("tpu", "TPU v99"),
                                           ("cpu", "cpu"),
                                           ("gpu", "TPU v5 lite")])
def test_peaks_unknown_device_is_an_error(platform, kind):
    from peaks import UnknownDevice, peaks_for
    with pytest.raises(UnknownDevice):
        peaks_for(platform, kind)
