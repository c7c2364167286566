"""The harness: everything a run does that belongs to no one cell.

This process is an IPython shell that drives the product's own entry
points (``%dist_init``, ``%%distributed``, ``%dist_pool``,
``%dist_serve``, the gateway client) and never touches the chip — only
``runtime.worker`` processes do.  Cells, configurations, traffic mixes,
metrics, readers and drivers are found by the names in
``BENCHMARK.json``; this file holds none of them.
"""

from __future__ import annotations

import contextlib
import importlib
import io
import json
import os
import re
import shutil
import signal
import sys
import tempfile
import time
import uuid

T_START = time.time()
BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
OUT_DIR = os.path.join(BENCH_DIR, "out")


class NoChip(Exception):
    """No accelerator, or fewer chips than the cell asks for."""


class RunFailed(Exception):
    pass


def load_json(*parts) -> dict:
    with open(os.path.join(BENCH_DIR, *parts)) as f:
        return json.load(f)


def load_benchmark() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def find_cell(bench: dict, name: str) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    raise SystemExit(f"no workload {name!r} in BENCHMARK.json")


def config_of(bench: dict, cell: dict, rehearse: bool) -> dict:
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    with open(os.path.join(ROOT, entry["file"])) as f:
        cfg = json.load(f)
    if rehearse:
        cfg = {**cfg, **cfg["rehearse"]}
    return cfg


def traffic_of(cell: dict, rehearse: bool) -> dict:
    t = load_json("traffic", cell["traffic"] + ".json")
    if rehearse:
        t = {**t, **t["rehearse"]}
    return t


def numbers_of(cfg: dict) -> dict:
    """What a worker cell needs of a configuration or traffic file: its
    plain values, not the prose and nested notes."""
    return {k: v for k, v in cfg.items()
            if isinstance(v, (int, float, str)) or v is None}


def metrics_for(bench: dict, cell: str, kind: str) -> list[dict]:
    """The cell's metrics of one kind (``end_to_end`` / ``per_layer``):
    those that list it, and those that list no cells."""
    return [m for m in bench[kind]
            if cell in m.get("workloads", [cell])]


def read_metric(name: str, obs: dict):
    """A metric's value through its own reader, or None where the
    reader finds nothing to read."""
    spec = load_json("metrics", name + ".json")
    reader = importlib.import_module("benchmarks.readers." + spec["reader"])
    return reader.read(obs, spec.get("args", {}))


def peak_for(kind: str) -> dict:
    peaks = load_json("peaks.json")
    if kind not in peaks:
        raise RunFailed(f"no peaks for device_kind {kind!r} in peaks.json")
    return peaks[kind]


def quantile(values, q: float) -> float:
    """Nearest-rank quantile over all values."""
    s = sorted(values)
    return s[min(len(s) - 1, max(0, int(q * len(s) + 0.5) - 1))]


# ----------------------------------------------------------------------


class _Tee(io.TextIOBase):
    def __init__(self, real):
        self.real, self.buf = real, io.StringIO()

    def write(self, s):
        self.real.write(s)
        self.buf.write(s)
        return len(s)

    def flush(self):
        self.real.flush()


class Bench:
    """One run: the shell, the fleet's processes, the spans and the
    record that goes to ``out/<cell>-<seed>.json``."""

    def __init__(self, args, cell, cfg, traffic):
        self.args, self.cell, self.cfg, self.traffic = args, cell, cfg, traffic
        self.chips = int(cell["chips"])
        self.backend = "cpu" if args.rehearse else "tpu"
        self.marker = f"NBD_BENCH_RUN={uuid.uuid4().hex}"
        os.environ.update([self.marker.split("=")])
        self.spans: dict[str, float] = {}
        self.record: dict = {"cell": cell["name"], "seed": args.seed,
                             "seconds": args.seconds, "trace": args.trace}
        self.device: dict | None = None
        self.ip = self.DM = None
        self.pool_dir: str | None = None    # a live pool's run dir
        self._pool_tmp: str | None = None   # ... removed once all ended
        self.trace_dir = os.path.join(
            OUT_DIR, f"trace-{cell['name']}-{args.seed}")

    # -- shell and magics ----------------------------------------------

    def shell(self):
        # Everything the program prints (banners, streamed cell output)
        # goes to stderr: stdout carries the result line.
        from IPython.testing.globalipapp import get_ipython, start_ipython
        with contextlib.redirect_stdout(sys.stderr):
            self.ip = start_ipython() or get_ipython()
            os.chdir(ROOT)
            self.ip.run_line_magic("load_ext", "nbdistributed_tpu")
        from nbdistributed_tpu.magics.magic import DistributedMagics
        self.DM = DistributedMagics

    def _captured(self, fn, *a) -> str:
        tee = _Tee(sys.stderr)
        with contextlib.redirect_stdout(tee):
            fn(*a)
        return tee.buf.getvalue()

    def magic(self, name: str, line: str = "") -> str:
        return self._captured(self.ip.run_line_magic, name, line)

    def run_cell(self, code: str, tag: str = "BENCH") -> list[dict]:
        """A ``%%distributed`` cell; one ``<tag> {json}`` line per rank."""
        out = self._captured(self.ip.run_cell_magic, "distributed", "", code)
        got = [json.loads(m) for m in re.findall(tag + r" (\{.*\})", out)]
        if len(got) != self.chips or any("error" in g for g in got):
            raise RunFailed(f"cell reported {len(got)}/{self.chips} ranks: "
                            + out.strip()[-3000:])
        return sorted(got, key=lambda g: g["rank"])

    @contextlib.contextmanager
    def span(self, name: str):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.spans[name] = self.spans.get(name, 0.0) \
                + time.perf_counter() - t0
            self.record.setdefault("phases", []).append(
                [name, round(time.time() - T_START, 3)])

    def new_pool_dir(self) -> str:
        self.pool_dir = self._pool_tmp = tempfile.mkdtemp(
            prefix="nbd_bench_pool_")
        return self.pool_dir

    # -- devices -------------------------------------------------------

    def check_devices(self, status: dict[int, dict]):
        """Every rank holds one device of the platform asked for, all
        different; else there is no chip to measure on."""
        seen = set()
        for r in range(self.chips):
            d = status.get(r) or {}
            devs = d.get("devices") or []
            if (len(devs) != 1 or devs[0].get("platform") != self.backend
                    or d.get("global_device_count") != self.chips):
                raise NoChip(f"rank {r} is not one {self.backend} device "
                             f"of {self.chips}: {d}")
            seen.add(devs[0]["id"])
        if len(seen) != self.chips:
            raise NoChip(f"ranks share devices: {sorted(seen)}")
        d0 = status[0]["devices"][0]
        self.device = {"platform": str(d0["platform"]),
                       "kind": str(d0["kind"]), "count": self.chips}

    def fleet_status(self) -> dict[int, dict]:
        resp = self.DM._comm.send_to_all("get_status", None, timeout=120)
        return {r: m.data or {} for r, m in resp.items()}

    # -- teardown ------------------------------------------------------

    def _marked(self) -> dict[int, str]:
        out = {}
        for pid in filter(str.isdigit, os.listdir("/proc")):
            if int(pid) == os.getpid():
                continue
            try:
                with open(f"/proc/{pid}/environ", "rb") as f:
                    if self.marker.encode() not in f.read():
                        continue
                with open(f"/proc/{pid}/stat") as f:
                    state = f.read().rsplit(")", 1)[1].split()[0]
            except OSError:
                continue
            if state != "Z":
                out[int(pid)] = state
        return out

    def cleanup(self):
        """Stop the pool and the fleet, wait for every process of this
        run to end, kill what is left."""
        with contextlib.suppress(Exception):
            if self.pool_dir:
                self.magic("dist_pool", f"stop --run-dir {self.pool_dir}")
        with contextlib.suppress(Exception):
            if self.DM is not None and (self.DM._comm is not None
                                        or self.DM._tenant is not None):
                self.magic("dist_shutdown")
        deadline = time.time() + 20
        while self._marked() and time.time() < deadline:
            time.sleep(0.25)
        for pid in self._marked():
            print(f"benchmark: killing leftover process {pid}",
                  file=sys.stderr)
            with contextlib.suppress(OSError):
                os.kill(pid, signal.SIGKILL)
        with contextlib.suppress(ChildProcessError):
            while os.waitpid(-1, os.WNOHANG)[0]:
                pass
        if self._pool_tmp:
            shutil.rmtree(self._pool_tmp, ignore_errors=True)

    def write_record(self):
        os.makedirs(OUT_DIR, exist_ok=True)
        path = os.path.join(
            OUT_DIR, f"{self.cell['name']}-{self.args.seed}.json")
        with open(path, "w") as f:
            json.dump(self.record, f)


def worker_prelude() -> str:
    """First lines of every worker cell: find the benchmark's modules."""
    return (f"import sys\nif {ROOT!r} not in sys.path: "
            f"sys.path.insert(0, {ROOT!r})\n")


def result_line(correct, attempted, failed, metrics, device, checks,
                breakdown=None, rehearsal=False) -> str:
    """The contract's last line; the numbers compared come last in it."""
    out = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed), "metrics": metrics, "device": device}
    if rehearsal:
        out["no_measurement"] = "rehearsal on the CPU: counts, no times"
    if breakdown:
        out["breakdown"] = breakdown
    out["compared"] = checks
    return json.dumps(out)
