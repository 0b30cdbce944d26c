"""Percent of the elements the delta rule's state holds that carry a
value (`apex_tpu.ops.delta_rule.stats()`: 100 x state_elems /
state_lane_elems, a head's d_k x d_v against the same rounded up to
the (8, 128) tiles a float32 (d_k, d_v) lies in, summed over this run's
traced calls).  75 at keys 96 and values 192 wide (192 values take two
tiles of 128 lanes), 100 at 128 x 128; what a wider or narrower head
would waste in the scans' states and the kernels' blocks.  None on a
program that has no such counter, or that traced no call."""


def compute(observed):
    from apex_tpu.ops import delta_rule

    calls = delta_rule.stats()
    if not calls.get("state_lane_elems"):
        return None
    return 100.0 * calls["state_elems"] / calls["state_lane_elems"]
