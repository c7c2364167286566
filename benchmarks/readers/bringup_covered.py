"""How much of the benchmark's ``fleet_attach_s`` stopwatch the
program's own stages explain: the daemon's start, the fleet's attach
(first ``Popen`` to every rank attached) and the tenant's attach, as
``serve_status.bringup.attach`` states them, over the stopwatch.  The
stopwatch also holds the benchmark's own facts cell.  Nothing where the
program keeps no such block (a commit before it did)."""

from benchmarks.readers import value


def read(obs: dict, args: dict):
    parts = [value.read(obs, {"path": "serve_status.bringup.attach." + k})
             for k in ("daemon_s", "attach_s", "tenant_attach_s")]
    watch = value.read(obs, {"path": "spans.fleet_attach_s"})
    if not watch or None in parts:
        return None
    return 100.0 * sum(parts) / watch
