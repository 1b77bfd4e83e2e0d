"""A cell of BENCHMARK.json cut to a size a CPU test run can hold: a
2-cluster site of 8 racks and 600 ticks in two chunks. Everything else
(rows, metrics, limits) is the cell's own."""
from chipbench import run

SMALL_SITE = dict(n_clusters=2, racks_per_cluster=8, servers_per_rack=8,
                  csw_per_cluster=2, n_fc=2, csw_ring_links=4,
                  fc_ring_links=8)
TICKS, CHUNK = 600, 300


def small_cell(name: str) -> run.Cell:
    cell = run.Cell(name)
    cell.cfg["site"] = dict(SMALL_SITE)
    cell.n_ticks, cell.chunk = TICKS, CHUNK
    return cell
