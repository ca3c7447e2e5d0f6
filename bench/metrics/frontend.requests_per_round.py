"""Requests the frontend coalesced per round in the window: the change
in its ``frontend.enqueued`` counter over the change in
``frontend.rounds``."""


def read(rec):
    fe = rec["frontend"]
    return fe["enqueued"] / fe["rounds"] if fe["rounds"] else None
