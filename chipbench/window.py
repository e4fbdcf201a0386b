"""Window accounting: from what the clients saw to the end-to-end
metrics. Pure arithmetic on recorded times, so it is tested on
synthetic records (tests/chipbench).

A record is one request as its client saw it: ``due`` (when the plan
wanted it sent; for a closed loop the moment its client became free),
``sent``, ``token_times`` (one arrival time per output token, in
order), ``done`` (ended with ``[DONE]`` and its exact token count).
The window is [t0, t1). Every sample belongs to the window by the time
its event happened, whichever request it is part of.
"""

from typing import Dict, List, Optional

from chipbench.stats import median


def out_tokens(records: List[Dict], t0: float, t1: float) -> int:
    """Output tokens whose chunk arrived inside the window."""
    return sum(1 for r in records for t in r["token_times"]
               if t0 <= t < t1)


def ttft_ms(records: List[Dict], t0: float, t1: float) -> List[float]:
    """Due time to first token, of requests whose first token arrived
    in the window."""
    return [1e3 * (r["token_times"][0] - r["due"]) for r in records
            if r["token_times"] and t0 <= r["token_times"][0] < t1]


def tpot_ms(records: List[Dict], t0: float, t1: float) -> List[float]:
    """(last token - first token) / (tokens - 1), of requests that
    finished in the window."""
    return [1e3 * (r["token_times"][-1] - r["token_times"][0])
            / (len(r["token_times"]) - 1) for r in records
            if r["done"] and len(r["token_times"]) > 1
            and t0 <= r["token_times"][-1] < t1]


def itl_ms(records: List[Dict], t0: float, t1: float) -> List[float]:
    """For every output token after a request's first that arrived in
    the window: the time since that request's previous token. Tokens
    that arrive together (one decode window's burst) give one gap of
    the burst's period and the rest near zero: that is what a stream's
    reader sees."""
    return [1e3 * (b - a) for r in records
            for a, b in zip(r["token_times"], r["token_times"][1:])
            if t0 <= b < t1]


def lag_ms(records: List[Dict], t0: float, t1: float) -> List[float]:
    """How late the generator sent: sent - due, of requests due in the
    window."""
    return [1e3 * (r["sent"] - r["due"]) for r in records
            if r.get("sent") is not None and t0 <= r["due"] < t1]


def end_to_end(records: List[Dict], t0: float, t1: float
               ) -> Dict[str, Optional[float]]:
    """The end-to-end metrics of one window (setup_s is the runner's).
    A metric without a sample is None and is left out of the line."""
    return {
        "ttft_p50_ms": median(ttft_ms(records, t0, t1)),
        "tpot_p50_ms": median(tpot_ms(records, t0, t1)),
        "out_tokens_per_s": out_tokens(records, t0, t1) / (t1 - t0),
    }
