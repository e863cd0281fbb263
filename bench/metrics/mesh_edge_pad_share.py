"""Share of the mesh's edge slots that are padding, in %:
``100 * (1 - mesh_edges / mesh_edge_slots)`` from the program's own gauges
(``repro.tracing.gauges``).  Every chip sweeps the same number of slots, set
by the most loaded shard, so padding is time that every chip spends.  A
program without the gauges, or a run that built no mesh engine, gives
nothing.
"""


def read(run):
    try:
        from repro.tracing import gauges
    except ImportError:
        return None
    got = gauges()
    if not got.get("mesh_edge_slots"):
        return None
    return 100.0 * (1.0 - got["mesh_edges"] / got["mesh_edge_slots"])
