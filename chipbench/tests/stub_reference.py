"""What a later PR may add as files of its own, in stub form: a reference
a configuration can name (``"reference": "tests.stub_reference"``), which
is ``reference.py``'s answer with the peak one sample off, so that a run
which really asked this module comes out not correct; and a counts
function a metric file can name as ``<module>:<function>``."""
from chipbench import kernel_counts, reference


def best_row(path, cfg, chunk_start, near_dm, control=False):
    out = reference.best_row(path, cfg, chunk_start, near_dm,
                             control=control)
    out["peak"] += 1
    return out


def twice_fdmt_counts(**shapes):
    """``kernel_counts.fdmt_counts`` with twice the bytes."""
    counts = kernel_counts.fdmt_counts(**shapes)
    return dict(counts, bytes=2 * counts["bytes"])
