"""Seconds of the scenario load from the program's content-addressed
bundle cache (`service.load_bundle`; on a checkout's first run the build
and the bundle's write too), the benchmark's own span (host clock)."""


def read(run):
    return run.spans.get("bundle_load")
