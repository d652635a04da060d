"""Host milliseconds per dispatch: the mean over the traced window's
``serve.execute`` spans of the span's duration less its ``serve.device``
child (assembly, argument handling and transfer, result copy, validation,
delivery)."""
from bench import spans


def read(run):
    sp = spans.of(run)
    ds = sp["host"]["dispatches"] if sp is not None else None
    if not ds:
        return None
    return sum(d["ms"] - d["phases"].get("serve.device", 0.0)
               for d in ds) / len(ds)
