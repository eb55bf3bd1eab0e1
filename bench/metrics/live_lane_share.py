"""Worker decode: live lanes over the lanes every decode step computes, in %."""


def read(m):
    lane_steps = sum(lanes * n for lanes, n, _, _ in m.rec.decode_calls)
    slot_steps = sum(slots * n for _, n, _, slots in m.rec.decode_calls)
    if slot_steps == 0:
        return None
    return 100.0 * lane_steps / slot_steps
