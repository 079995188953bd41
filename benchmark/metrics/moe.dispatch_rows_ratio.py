"""What dropping nothing costs in padded rows: the rows of one expert layer's
sorted-assignment buffer as the step allocated it (``moe_buffer_rows``) over
the assignments it expects under uniform routing (``moe_expected_rows`` =
tokens x top_k x held / experts), both static attributes of the program's
``executor.train_step`` span. The products walk the whole buffer, so this
is also the rows they process over the rows they need: 1 would be a buffer
of the expected load alone, and the worst case of a layer that holds a
quarter of the experts is 4. Program span."""
from lib import spans


def read(run):
    for r in reversed(spans.records()):
        a = r["args"]
        if r["name"] == spans.STEP and a.get("moe_expected_rows"):
            return a["moe_buffer_rows"] / a["moe_expected_rows"]
    return None
