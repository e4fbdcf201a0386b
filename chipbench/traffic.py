"""The one traffic generator. A traffic mix is a data file of
parameters (``chipbench/traffic/<name>.json``); this module turns it,
a seed, the window length and (open loop) the cell's rate into a plan.

Lengths are not drawn independently. Each distribution is given as
quantile knots, and the plan holds its quantile grid: ``points`` values
at the quantiles (i + 0.5) / points. ``--seed`` only permutes the grid
(prompts, outputs and arrival gaps each with their own permutation) and
draws the prompt bytes, so every seed offers the same multiset of work
in another order. The permuted cycle then repeats unchanged: any
interval as long as one cycle holds every element exactly once,
wherever it starts.

Open loop: ``points`` is rate x seconds, the gaps are the quantile grid
of the exponential distribution scaled so that one cycle lasts exactly
``seconds``: the arrivals are exponential in their marginal and every
window of ``seconds`` holds exactly ``points`` of them.
"""

import dataclasses
import math
import random
from typing import Dict, Iterator, List, Optional


def quantile_grid(spec: Dict, points: int) -> List[int]:
    """``spec``: {"knots": [[q, value], ...], "interp": "linear"|"log"};
    knots ascend from q=0 to q=1. Returns ``points`` whole numbers."""
    knots = spec["knots"]
    if knots[0][0] != 0 or knots[-1][0] != 1 or any(
            b[0] <= a[0] or b[1] < a[1] for a, b in zip(knots, knots[1:])):
        raise ValueError(f"knots must ascend from q=0 to q=1: {knots}")
    log = spec.get("interp", "linear") == "log"
    out = []
    for i in range(points):
        q = (i + 0.5) / points
        for (q0, v0), (q1, v1) in zip(knots, knots[1:]):
            if q <= q1:
                f = (q - q0) / (q1 - q0)
                v = (math.exp(math.log(v0) + f * (math.log(v1)
                                                  - math.log(v0)))
                     if log else v0 + f * (v1 - v0))
                out.append(int(round(v)))
                break
    return out


def exponential_gaps(points: int, total_s: float) -> List[float]:
    """Quantile grid of the exponential distribution, scaled to sum to
    ``total_s``."""
    raw = [-math.log(1.0 - (i + 0.5) / points) for i in range(points)]
    k = total_s / sum(raw)
    return [g * k for g in raw]


@dataclasses.dataclass(frozen=True)
class Planned:
    index: int              # position in the stream of planned requests
    prompt_tokens: int
    output_tokens: int
    due_s: Optional[float]  # open loop: offset from the schedule's start
    lead_in: bool = False


@dataclasses.dataclass
class Plan:
    loop: str                       # "closed" | "open"
    clients: int                    # closed loop
    lead_in: List[Planned]          # closed loop: one per client
    lead_in_s: float                # open loop: schedule start to window
    prompts: List[int]              # one cycle, permuted
    outputs: List[int]
    gaps: List[float]               # open loop, one cycle, permuted
    seed: int

    def stream(self) -> Iterator[Planned]:
        """The planned requests in order, without end."""
        n, t, k = len(self.prompts), 0.0, 0
        while True:
            due = None
            if self.loop == "open":
                t += self.gaps[k % n]
                due = t
            yield Planned(k, self.prompts[k % n], self.outputs[k % n], due)
            k += 1

    def prompt_ids(self, req: Planned) -> List[int]:
        """The request's prompt: byte tokens (one byte is one token of
        the byte tokenizer), drawn from the seed and the request's
        place in the stream, so no two prompts share a prefix beyond
        chance."""
        rng = random.Random(self.seed * 1_000_003
                            + (req.index + 1) * (-1 if req.lead_in else 1))
        return [rng.randrange(256) for _ in range(req.prompt_tokens)]


def make_plan(traffic: Dict, seed: int, seconds: float,
              rate_rps: Optional[float] = None) -> Plan:
    loop = traffic["loop"]
    if loop == "open":
        if not rate_rps or rate_rps <= 0:
            raise ValueError("an open-loop cell needs rate_rps in its "
                             "chipbench/cells/<cell>.json")
        points = max(1, round(rate_rps * seconds))
    elif loop == "closed":
        points = int(traffic["points"])
    else:
        raise ValueError(f"loop must be closed or open, not {loop!r}")
    prompts = quantile_grid(traffic["prompt_tokens"], points)
    outputs = quantile_grid(traffic["output_tokens"], points)
    gaps = exponential_gaps(points, seconds) if loop == "open" else []
    for k, series in enumerate((prompts, outputs, gaps)):
        random.Random(seed * 7919 + k).shuffle(series)
    clients = int(traffic.get("clients", 0))
    lead_in = []
    if loop == "closed":
        # client i's first request runs (i+1)/N of the mean output, so
        # that completions (and with them prefills) are spread evenly
        # over one cycle when the window opens
        mean_out = sum(outputs) / len(outputs)
        mid_prompt = sorted(prompts)[len(prompts) // 2]
        lead_in = [Planned(i, mid_prompt,
                           max(1, round(mean_out * (i + 1) / clients)),
                           None, lead_in=True) for i in range(clients)]
    return Plan(loop=loop, clients=clients, lead_in=lead_in,
                lead_in_s=float(traffic.get("lead_in_seconds", 0.0)),
                prompts=prompts, outputs=outputs, gaps=gaps, seed=seed)
