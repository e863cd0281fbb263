"""Bytes one chip receives over the interconnect per colouring, from the
program's own accounting (``repro.tracing.gauges``): the mesh backend's
``mesh_collective_bytes`` for one launch over ``mesh_chunk``, the launch's
colourings.  Blocking all-gathers and the ``ppermute`` ring move the same
bytes.  A program without the gauges, or a run that built no mesh engine,
gives nothing.
"""


def read(run):
    try:
        from repro.tracing import gauges
    except ImportError:
        return None
    got = gauges()
    if not got.get("mesh_chunk"):
        return None
    return got["mesh_collective_bytes"] / got["mesh_chunk"]
