"""Share of the schedule's payload bytes that carry an accepted packet;
the rest is inert padding (unfilled ring slots, the pad to the
128-packet block, and rows padded to a multiple of 8)."""


def read(obs):
    shipped = sum(r["payload_bytes"] for r in obs.rounds)
    if not shipped:
        return None
    return 100.0 * sum(r["useful_payload_bytes"] for r in obs.rounds) / shipped
