"""Serving: build HyperServe for a deployment, warm it, and the client.

The window drives ``HyperServe.step_once`` through ``Supernode.serve``,
the program's normal serving path.  The benchmark's loop is the client
(:class:`Client`): a generator (``generators/<kind>.py``) decides when it
submits each request; the client steps the engine and stamps every token
when ``step_once`` hands it back.  All times are ``time.perf_counter``
seconds.
"""
from __future__ import annotations

import contextlib
import dataclasses
import random
import time
from typing import Dict, List, Optional, Tuple

from chipbench.costs import Dims

now = time.perf_counter


# ---------------------------------------------------------------------------
# the program's side: configuration, parameters, server
# ---------------------------------------------------------------------------
def program_config(conf: dict):
    """The program's ModelConfig with every size from the config file."""
    import dataclasses as dc

    from repro.configs.base import get_config
    dims = Dims.from_config(conf)
    return dc.replace(
        get_config(conf["program_name"]), num_layers=dims.layers,
        d_model=dims.d_model, num_heads=dims.heads,
        num_kv_heads=dims.kv_heads, head_dim=dims.head_dim, d_ff=dims.d_ff,
        vocab_size=dims.vocab, rope_theta=dims.rope_theta,
        norm_eps=dims.norm_eps, tie_embeddings=dims.tied,
        qkv_bias=dims.qkv_bias, dtype=conf["torch_dtype"])


def program_params(weights: dict) -> dict:
    """The benchmark's weights in the program's tree (same arrays)."""
    out = {k: v for k, v in weights.items() if k != "layers"}
    out["seg0"] = (weights["layers"],)
    return out


def serve_config(deploy: dict, dims: Dims):
    """ServeConfig from the deployment's values; every other knob keeps
    the program's default."""
    from repro.configs.base import ServeConfig
    s = deploy["serve"]
    block = ServeConfig().block_size
    pool_tokens = s["kv_pool_bytes"] // dims.kv_bytes_per_token
    return ServeConfig(
        max_slots=s["max_slots"],
        max_blocks_per_req=-(-s["max_context"] // block),
        num_blocks=pool_tokens // block + 1,        # + the null block
        prefill_chunk=s["prefill_chunk"],
        prefill_batch=s["prefill_batch"],
        prefill_chunks_per_step=s["prefill_chunks_per_step"],
        max_queue=s["max_queue"])


def build_server(devices, conf: dict, deploy: dict, weights: dict):
    from repro.api import Supernode, plans
    cfg = program_config(conf)
    scfg = serve_config(deploy, Dims.from_config(conf))
    session = Supernode(None, devices=list(devices[:1]))
    return session.serve(cfg, program_params(weights),
                         plan=plans.serve(serve=scfg))


def warm_up(server, deploy: dict, vocab: int, seed: int) -> int:
    """Serve 1, then 2, then up to ``prefill_batch`` requests at once, so
    every prefill row bucket, every row's first-token sampling and the
    decode step compile before the window.  Returns tokens served."""
    s = deploy["serve"]
    rng = random.Random(seed)
    sizes, n, served = [], 1, 0
    while n < s["prefill_batch"]:
        sizes.append(n)
        n *= 2
    sizes.append(s["prefill_batch"])
    for n in sizes:
        rids = [server.submit([rng.randrange(1, vocab)
                               for _ in range(s["prefill_chunk"] + 3 + i)], 3)
                for i in range(n)]
        left = set(rids)
        while left:
            for rid, _ in server.step_once():
                served += 1
                if len(server.result(rid)) >= 3:
                    left.discard(rid)
    return served


# ---------------------------------------------------------------------------
# the window
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class Flight:
    """One request as the client sees it."""
    index: int
    prompt: List[int]
    max_new: int
    due: float                      # absolute perf_counter time it was due
    rid: Optional[int] = None
    rejected: bool = False
    t_submit: Optional[float] = None
    tokens: List[int] = dataclasses.field(default_factory=list)
    times: List[float] = dataclasses.field(default_factory=list)

    @property
    def done(self) -> bool:
        return len(self.tokens) >= self.max_new


@dataclasses.dataclass
class Step:
    t0: float
    t1: float
    decode_lengths: List[int]       # context of each decode row
    first_tokens: int               # tokens that ended a prompt


@dataclasses.dataclass
class Window:
    t_open: float
    t_close: float = 0.0            # end of the last step begun in the window
    flights: List[Flight] = dataclasses.field(default_factory=list)
    steps: List[Step] = dataclasses.field(default_factory=list)
    lateness: List[float] = dataclasses.field(default_factory=list)
    trace_from: Optional[float] = None
    trace_to: Optional[float] = None

    @property
    def seconds(self) -> float:
        return self.t_close - self.t_open


class Client:
    """Steps a server and stamps tokens; with ``trace_dir``, annotates its
    calls into the program and profiles the whole window."""

    def __init__(self, server, *, trace_dir: Optional[str] = None):
        self.server = server
        self.annotate = trace_dir is not None
        self.trace_dir = trace_dir
        self.by_rid: Dict[int, Flight] = {}

    def span(self, name: str):
        if not self.annotate:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    def submit(self, fl: Flight, t: float) -> None:
        from repro.serve.api import RequestRejected
        fl.t_submit = t
        try:
            with self.span("chipbench.submit"):
                fl.rid = self.server.submit(fl.prompt, fl.max_new)
        except RequestRejected:
            fl.rejected = True
            return
        self.by_rid[fl.rid] = fl

    def step(self, win: Window) -> None:
        t0 = now()
        with self.span("chipbench.step"):
            events = self.server.step_once()
        t1 = now()
        dec, first = [], 0
        for rid, tok in events:
            fl = self.by_rid[rid]
            if fl.tokens:
                dec.append(len(fl.prompt) + len(fl.tokens))
            else:
                first += 1
            fl.tokens.append(tok)
            fl.times.append(t1)
        win.steps.append(Step(t0, t1, dec, first))

    def open(self, at: Optional[float] = None) -> Window:
        """Start the profiler, where asked, and open the window (at ``at``,
        where a schedule fixed the time)."""
        if self.trace_dir is not None:
            import jax
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0     # host annotations only
            opts.host_tracer_level = 1
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        win = Window(t_open=now() if at is None else at)
        if self.trace_dir is not None:
            win.trace_from = win.t_open
        return win

    def close(self, win: Window) -> None:
        win.t_close = now()
        if win.trace_from is not None:
            import jax
            win.trace_to = now()
            jax.profiler.stop_trace()


def program_prefills(events: List[dict], by_rid: Dict[int, Flight],
                     t_lo: float, t_hi: float, chunk: int, offset_s: float
                     ) -> List[List[Tuple[int, int]]]:
    """(start, n) rows of every prefill call the program's ``serve.prefill``
    spans record inside [t_lo, t_hi].  Each listed request advances by one
    chunk from where its previous chunk ended (prompts here share no
    prefix with the prefix cache, so every prompt starts at 0)."""
    done: Dict[int, int] = {}
    calls = []
    for ev in events:
        if ev.get("name") != "serve.prefill":
            continue
        rows = []
        for rid in ev["args"]["rids"]:
            start = done.get(rid, 0)
            n = min(chunk, len(by_rid[rid].prompt) - start)
            done[rid] = start + n
            rows.append((start, n))
        if t_lo <= ev["ts"] / 1e6 + offset_s <= t_hi:
            calls.append(rows)
    return calls
