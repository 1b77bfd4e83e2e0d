"""The switch kernel's share of its roofline: the least time its calls
in the traced slice could take, the bytes of the ``switch_step``
contract (chipbench/kernel_bytes.py) over HBM bandwidth
(chipbench/peaks.json), divided by the time they took."""
from chipbench import devtrace, kernel_bytes


def read(ctx):
    tr = ctx["trace"]
    ticks = devtrace.ticks(tr)
    if not ticks or tr["kernel_s"] <= 0.0:
        return None
    kind = ctx["device_kind"]
    if kind not in ctx["peaks"]:
        raise KeyError(f"chipbench/peaks.json has no {kind!r}")
    cell = ctx["cell"]
    rows = -(-len(cell.rows) // cell.chips)
    least = ticks * kernel_bytes.bytes_per_tick(
        cell.cfg["site"], rows) / ctx["peaks"][kind]["hbm_bytes_per_s"]
    return 100.0 * least / tr["kernel_s"]
