"""PR 38, call H: where do the garbage collector's full collections land in a run's set-up?
Put on PYTHONPATH (``PYTHONPATH=benchmarks/calls/pr38_gcprobe python benchmarks/chipbench/run.py ...``:
run.py is started as ever, as the script), this logs to ``$GCPROBE_OUT`` one JSON line for every
collection of generation 2 (start, seconds, objects collected, the younger generations' collections
and seconds since the last line) and, once jax is imported, one line for every compile stage JAX
reports that lasts 50 ms or more (event, fun_name, start, seconds): on the parent, which has no
recorder, as on the change. Times are ``time.time_ns()``, the recorder's clock."""
import gc
import json
import os
import sys
import time

_out = open(os.environ.get("GCPROBE_OUT", "gcprobe.jsonl"), "a", buffering=1)
_state = {"t": 0, "young_n": 0, "young_ns": 0, "listening": False}


def _on_span(event, start_s, end_s, **kw):
    if end_s - start_s >= 0.05:
        _out.write(json.dumps({"event": event, "fun_name": str(kw.get("fun_name")), "start_ns": int(start_s * 1e9),
                               "seconds": round(end_s - start_s, 4)}) + "\n")


def _on_gc(phase, info):
    now = time.time_ns()
    if phase == "start":
        _state["t"] = now
        if not _state["listening"] and "jax" in sys.modules and hasattr(sys.modules["jax"], "monitoring"):
            _state["listening"] = True
            sys.modules["jax"].monitoring.register_event_time_span_listener(_on_span)
        return
    spent = now - _state["t"]
    if info["generation"] < 2:
        _state["young_n"] += 1
        _state["young_ns"] += spent
        return
    _out.write(json.dumps({"gc": 2, "start_ns": _state["t"], "seconds": round(spent / 1e9, 4), "collected": info["collected"],
                           "tracked": len(gc.get_objects()), "young_n": _state["young_n"],
                           "young_s": round(_state["young_ns"] / 1e9, 4)}) + "\n")
    _state["young_n"] = _state["young_ns"] = 0


gc.callbacks.append(_on_gc)
