"""The HBM traffic the Algorithm-2 round close needs."""
import importlib.util
import os

import tiny


def reader(name):
    path = os.path.join(tiny.BENCH, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_close_bytes_reads_and_writes_both_live_banks():
    su = reader("stats_update_roofline")
    # 8 channels x 20 live partitions x 65 columns x 4 bytes, two banks,
    # each read once and written once
    assert su.close_bytes(20, 64) == 2 * 2 * 8 * 20 * 65 * 4 == 166_400
    assert su.close_bytes(40, 64) == 2 * su.close_bytes(20, 64)


def test_roofline_share_from_kernel_time():
    su = reader("stats_update_roofline")

    class R:
        conf = {"deployment": {"grid_size": 64}}
        peaks = {"hbm_bytes_per_s": 819e9}

        def op_ns(self, pattern):
            return 1e6 if pattern.search("%stats_update_kernel.1 = f32") \
                else 0.0

        def rounds_in_window(self):
            return [20, 20]

    share = su.read(R())
    assert abs(share - 100 * 2 * 166_400 / 819e9 / 1e-3) < 1e-9

    class Silent(R):
        def op_ns(self, pattern):
            return 0.0
    assert su.read(Silent()) is None
