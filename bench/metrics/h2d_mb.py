"""Megabytes (1e6 bytes) per round of the drain schedule's arrays that
a round ships to the device (idx, weights, payloads and, on the q8
wire, scales), counted from the schedule's shapes."""


def read(obs):
    if not obs.n_rounds:
        return None
    return sum(r["h2d_bytes"] for r in obs.rounds) / 1e6 / obs.n_rounds
