"""Share of the traced serving call's slot-ticks that decode a token (%):
decoded tokens of its requests (each request's first token comes from
prefill) over slots x protected decode ticks, both counted by the program.
Slots idle while a call drains its last requests, or until a finished
request's slot is released at the next flush edge, lower it."""


def read(d):
    return d.call["occupancy_pct"]
