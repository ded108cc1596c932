"""lookup_predict_device_ms: device milliseconds per request under the
``predict`` scope of ``repro.index.index.lookup_impl`` (the model's
prediction of each query's window), the union of that scope's op
intervals in the trace.  None where the trace has no such scope (a
program compiled without its scopes).  Moves ops_per_s."""

from benchmarks.chip.readers import per_request_ms, trace_value


def read(ctx):
    return per_request_ms(ctx, (trace_value(ctx, "scopes") or {}).get("predict"))
