"""Host clock around the warm launch, ending in its host result: compile or
compile-cache load, plus one launch of the cell's shape."""


def read(run):
    return run.spans.get("first_launch")
