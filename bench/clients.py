"""Request clients: the base class and what the traffic kinds share.

A client turns the generator's plain arrays into the program's own
objects, issues one closed-loop request through the program's public
entry point, keeps what the request returned, and after the window
compares every returned answer with the plain reference.  Each traffic
`kind` has a file of its own, `bench/kinds/<kind>.py`, whose `CLIENT` is
a subclass of `Client`; `client_class` finds it by the kind's name, so a
new kind is a new file.

    client = client_class(spec["kind"])(carina, cfg, spec, seed, chips)
    client.build()              # inputs from the seed
    client.warm_up()            # brings the program to steady state
    units = client.request(k)   # the timed call; cases (or re-plans) done
    checks = client.check()     # {name: (value, limit)} over the window

`precision` is the program's dtype policy; the configuration's own is the
default, and the lower-precision control passes the program's "mixed".
"""
from __future__ import annotations

import contextlib
import functools
import importlib.util
import os
from typing import Dict, List, Optional, Tuple

import numpy as np

import roofline
import xplane

#: The checkout this file lies in.
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@contextlib.contextmanager
def compile_cache_off():
    """The persistent compile cache off for the compiles inside."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def program_campaigns(carina, cfg: dict):
    """(bands, [Campaign]) built from the configuration's numbers."""
    b = cfg["bands"]
    bands = carina.TimeBands(
        peak=tuple(tuple(r) for r in b["peak"]),
        load_sensitive=tuple(tuple(r) for r in b["load_sensitive"]),
        shoulder=tuple(tuple(r) for r in b["shoulder"]))
    out = []
    for c in cfg["campaigns"]:
        w = c["workload"]
        wl = carina.OEMWorkload(w["name"], int(w["n_scenarios"]),
                                rate_at_full=0.0,
                                batch_overhead_s=w["batch_overhead_s"],
                                measured_hours=w["measured_hours"],
                                measured_kwh=w["measured_kwh"])
        out.append(carina.Campaign(wl, machine=carina.MachineProfile(
            **c["machine"]), bands=bands, start_hour=c["start_hour"]))
    return bands, out


def scan_settings(cfg: dict) -> dict:
    """The reference scan's grid settings from the configuration."""
    e = cfg["engine"]
    sph = e["slots_per_hour"]
    if sph != 1:
        raise ValueError("the reference steps hourly slots only")
    return {"start_hour": cfg["campaigns"][0]["start_hour"],
            "buckets": e["progress_buckets"],
            "chunk_slots": e["chunk_days"] * 24 * sph,
            "finish_frac": e["finish_frac"],
            "max_slots": e["max_days"] * 24 * sph}


def useful_slots(runtime_h) -> int:
    """Hourly slots in which campaigns of these runtimes did work."""
    return int(np.ceil(np.asarray(runtime_h, dtype=float) - 1e-9).sum())


class Client:
    kernel = "plain"        # chunk kernel the timed path runs
    #: device modules of that kernel in the trace (`kernel_roofline.sweep`)
    kernel_modules = xplane.KERNEL_MODULES
    #: faults `test_bench_faults.py` plants under this kind's timed path
    faults = ("state_unchanged", "half_left_out", "answer_altered")

    def __init__(self, carina, cfg: dict, spec: dict, seed: int, chips: int,
                 precision: str = ""):
        self.carina = carina
        self.cfg = cfg
        self.spec = spec
        self.seed = int(seed)
        self.chips = int(chips)
        self.precision = precision or cfg["engine"]["precision"]
        self.records: List[Tuple[int, object]] = []

    def warm_up(self) -> None:
        """Request 0 compiles or loads every program the window's
        requests run (their shapes do not depend on the seed); its
        answers are not checked."""
        self.request(0)
        self.records.clear()

    def work(self) -> Dict[str, int]:
        """Useful work of the window's requests, for the work function."""
        return {}

    def useful_bytes(self, dtype: str) -> Optional[int]:
        """Bytes the window's useful work had to read (`roofline.py`);
        None when it holds no useful lane-slot.  A kind with a kernel of
        its own counts that kernel's bytes here."""
        work = self.work()
        if not work.get("lane_slots"):
            return None
        return roofline.useful_bytes(self.kernel, dtype=dtype, **work)

    def plant(self, fault: str, monkeypatch) -> None:
        """Break this kind's timed path with `fault`, one of `faults`, for
        the tests (`planted.py` has the faults under today's chunk
        kernels).  A kind with a kernel of its own plants here what
        breaks that kernel."""
        import planted
        planted.FAULTS[fault](monkeypatch)

    def size(self) -> int:
        """Units one request asks for."""
        return 1

    def nothing_to_compare(self) -> Dict[str, Tuple[float, float]]:
        """The checks of a window that returned no answer."""
        return {"max_rel_gap": (0.0, self.spec["check"]["max_rel_gap"]),
                "unscored": (0.0, 0.0)}


@functools.lru_cache(maxsize=None)
def load_attr(path: str, attr: str, prefix: str):
    """`attr` of the Python file at `path`, loaded once per process as a
    module named `prefix` + the file's stem."""
    name = prefix + os.path.basename(path)[:-3].replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return getattr(mod, attr)


def client_class(kind: str, root: str = ROOT) -> type:
    """The `CLIENT` of `bench/kinds/<kind>.py` in the checkout `root`,
    loaded once per process (so a test that patches the class patches
    what the run builds)."""
    path = os.path.join(root, "bench", "kinds", kind + ".py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"bench: traffic kind {kind!r} has no client "
                                f"file {path}")
    return load_attr(os.path.abspath(path), "CLIENT", "bench_kind_")
