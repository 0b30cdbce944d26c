"""Device milliseconds a step the flat view of the state costs, first
device: slicing the flat buffer into the parameters (`unflatten`) and
everything owned under `optimizer` except the `adam_flat*` kernel
itself: flattening the gradients, the casts, and the copies and
flat-buffer updates the compiler puts before the kernel (ROADMAP S3)."""

from benchmarks.lib import owners


def compute(observed):
    unflatten = owners.ms(observed, owner=r"unflatten$")
    if unflatten is None:
        return None
    return unflatten + owners.ms(observed, owner=r"optimizer(/|$)",
                                 but_name=r"adam_flat")
