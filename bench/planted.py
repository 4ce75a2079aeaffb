"""Faults planted under the timed path of today's chunk kernels, for the
benchmark's tests (`Client.plant`): a chunk step that hands back its
state unchanged, half of a batch's answers replaced by the mean of the
other half, and one answer altered where it is produced.  Each takes
pytest's `monkeypatch`, so the test undoes it."""
import numpy as np

#: where each chunk kernel's carried state starts among its inputs
STATE_AT = {"_scan_chunk_jax": 7, "_scan_chunk_jax_coupled": 10}


def state_unchanged(monkeypatch):
    from repro.core import engine_jax
    for name, first in STATE_AT.items():
        n = 6 if "coupled" in name else 5
        monkeypatch.setattr(engine_jax, name,
                            lambda *a, _i=first, _n=n, **kw: a[_i:_i + _n])


def half_left_out(monkeypatch):
    from repro.core import engine_jax
    orig = engine_jax.summarize_plan

    def summarize(plan, state):
        out = orig(plan, state)
        h = len(out) // 2
        for f in ("runtime_h", "energy_kwh", "co2_kg"):
            mean = float(np.mean([getattr(r, f) for r in out[:h]]))
            for r in out[h:]:
                setattr(r, f, mean)
        return out
    monkeypatch.setattr(engine_jax, "summarize_plan", summarize)


def answer_altered(monkeypatch):
    from repro.core import engine_jax
    orig = engine_jax.summarize_plan

    def summarize(plan, state):
        out = orig(plan, state)
        out[len(out) // 2].energy_kwh *= 1.0 + 1e-6
        return out
    monkeypatch.setattr(engine_jax, "summarize_plan", summarize)


FAULTS = {"state_unchanged": state_unchanged, "half_left_out": half_left_out,
          "answer_altered": answer_altered}
