"""Process start to window start (host clock): imports, device start-up, graph
generation and upload, engine construction, compile or cache load, warm launch."""


def read(run):
    return run.spans.get("setup")
