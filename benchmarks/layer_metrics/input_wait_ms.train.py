"""Time the train loop waited for its next batch, a step: total length of the
``rlt.train.input_wait`` spans over the count of ``rlt.train.step`` spans in
the trace."""
from benchmarks.program_trace import INPUT_WAIT, per_step_ms, spans


def read(facts):
    return per_step_ms(spans(facts.get("trace_path")), INPUT_WAIT)
