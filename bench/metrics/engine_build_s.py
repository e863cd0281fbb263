"""Host clock around the ``CountingEngine(...)`` constructor: plan, cost model,
backend pick, operand build and transfer."""


def read(run):
    return run.spans.get("engine_build")
