"""A plain model of what any correct fail-over leaves behind: given the
script of what the peers do, and the tries a bounded sync made, whether
the reasons the tries ended for and the store's height after each are
ones a correct client can have had.  Pure Python; it imports nothing of
the program, and it knows nothing of segments, pipelines or programs: a
client may verify and commit in runs of any length, so the height after
a try is held between bounds, not to a number.

The chain is rounds 1..`backlog`.  A step of the script is what the
k-th stream a request opens is served with, whoever serves it (the last
step also serves every stream past the script's end):

    {"kind": "sound"}
    {"kind": "abort", "after_messages": m}   the stream raises after m
                                             messages of `chunk` rounds
    {"kind": "corrupt_row", "round": r}      rounds below r, then a
                                             clean end
    {"kind": "liar", "round": r}             round r's signature false,
                                             everything else sound
    {"kind": "flips", "rounds": [...]}       those rounds' signatures
                                             false

A try is `{"live": bool, "end": reason, "height": rounds the store
holds after it}`, in the order the request made them.  An unreachable
peer's try ends `unreachable` and moves nothing.  A live peer's try is
served from the round after the height before it, and what a correct
client makes of it:

    what the stream holds                 end             height after
    every round to the backlog's end      done            backlog
    rounds to s, then an exception        dropped         before..s
    rounds to s < backlog, a clean end    ended_short     before..s
    a false signature at round f          verify_failed   before..f-1

(a lie or a damaged row that lies behind the height already held is
never served: the stream is then as sound as its rest).  The request is
true exactly if the last height is the backlog; it may be false only
when every peer was tried.
"""

from __future__ import annotations


def served(step: dict, start: int, backlog: int, chunk: int):
    """(end, highest height a correct client may hold after it) of a
    stream served from round `start` under `step`."""
    kind = step["kind"]
    if kind == "abort":
        last = start - 1 + step["after_messages"] * chunk
        if last < backlog:
            return "dropped", last
    elif kind == "corrupt_row":
        if start <= step["round"] <= backlog:
            return "ended_short", step["round"] - 1
    elif kind in ("liar", "flips"):
        bad = [r for r in step.get("rounds", [step.get("round")])
               if start <= r <= backlog]
        if bad:
            return "verify_failed", min(bad) - 1
    elif kind != "sound":
        raise ValueError(f"unknown step {kind!r}")
    return "done", backlog


def violations(script: list, tries: list, backlog: int, chunk: int,
               returned: bool, peers: int) -> list[str]:
    """What of `tries` (and of what the request returned, having
    `peers` peers to try) no correct fail-over under `script` can have
    left; empty where all of it can."""
    out = []
    height, streams = 0, 0
    for i, t in enumerate(tries, 1):
        if not t["live"]:
            want, top, low = "unreachable", height, height
        else:
            step = script[min(streams, len(script) - 1)]
            streams += 1
            want, top = served(step, height + 1, backlog, chunk)
            low = top if want == "done" else height
        if t["end"] != want:
            out.append(f"try {i} ended {t['end']!r}, the model says {want!r}")
        if not low <= t["height"] <= top:
            out.append(f"try {i} left the store at {t['height']}, the model "
                       f"says {low}..{top}")
        if height >= backlog:
            out.append(f"try {i} was made with the store at the target")
        height = t["height"]
    if returned != (height >= backlog):
        out.append(f"the request returned {returned} with the store at "
                   f"{height} of {backlog}")
    if not returned and len(tries) != peers:
        out.append(f"the request returned false after {len(tries)} of "
                   f"{peers} peers")
    return out

