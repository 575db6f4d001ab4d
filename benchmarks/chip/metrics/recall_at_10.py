"""Recall@10 against brute force over every answer in the window."""


def read(rec):
    return rec["recall_at_10"]
