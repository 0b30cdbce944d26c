"""Host seconds in `.compile()`: XLA's compile when the persistent
cache is cold, a read of the cache when it is warm."""


def compute(observed):
    return observed["spans"].get("compile_s")
