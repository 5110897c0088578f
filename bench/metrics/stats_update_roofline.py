"""Kernel: the Pallas ``stats_update`` round close against its HBM
roofline, in %.

The least time the pass can take is the HBM traffic that Algorithm 2's
prefix-sum pass needs, over the chip's HBM bandwidth: read and write
the live (NUM_CH, P_live, G + 1) float32 row bank and column bank of
each round close (:func:`close_bytes`, the same whatever implements the
pass).  Divided by the kernel's device time in the traced window."""
import re

NUM_CH = 8
KERNEL = re.compile(r"stats_update")


def close_bytes(p_live: int, grid_size: int) -> int:
    """Bytes one round close must move: two banks, read and written."""
    return 2 * 2 * NUM_CH * p_live * (grid_size + 1) * 4


def read(r):
    kernel_ns = r.op_ns(KERNEL)
    g = r.conf["deployment"]["grid_size"]
    rounds = r.rounds_in_window()
    if kernel_ns <= 0 or not rounds:
        return None
    need_s = sum(close_bytes(p, g) for p in rounds) \
        / r.peaks["hbm_bytes_per_s"]
    return 100.0 * need_s / (kernel_ns / 1e9)
