"""Median time to first token, from when the request was due.  In an open
loop below the knee it sits under the judged tail; behind a backlog that
never empties it is queue wait and swings with the smallest change."""

from benchmark.harness import stats


def read(run):
    return stats.median(run["ttft_ms"])
