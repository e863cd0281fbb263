"""Share of the ``edges`` backend's aggregation lanes that carry columns, in
%: ``100 * edges_lane_used / edges_lane_slots`` from the program's own gauges
(``repro.tracing.gauges``) for one launch.  The backend flattens each
``(n, B, w)`` slice to ``B * w`` columns and pads them to whole 128-lane rows
on a TPU, and a pass over the edges costs a row whatever its fill, so the
share is the aggregation's useful work per row paid.  A program without the
gauges, or a run that built no ``edges`` engine, gives nothing.
"""


def read(run):
    try:
        from repro.tracing import gauges
    except ImportError:
        return None
    got = gauges()
    if not got.get("edges_lane_slots"):
        return None
    return 100.0 * got["edges_lane_used"] / got["edges_lane_slots"]
