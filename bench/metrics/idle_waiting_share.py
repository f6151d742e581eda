"""Share of the traced device slice, in %, in which the server held no
batch and so waited for the batcher to form one (``spans.waiting_share``:
the program's ``serve.batch`` spans on the host clock)."""
import spans


def read(run):
    w = spans.waiting_share(run)
    return None if w is None else 100.0 * w
