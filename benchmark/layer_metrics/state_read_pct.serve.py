"""Of the cache bytes the window's rounds had to move, the share that is
recurrent state: from every ``decode_round`` span inside the window, its
``state_rows`` (a state read once and written once a launched row and
state layer) against its ``latent_rows`` (a latent row read once a cached
token and latent layer), each times what the configuration's widths make
it weigh. It says whether the traffic still makes the state layers the
larger reader: the state's bytes grow with the rows of a round, the latent
rows' with their contexts. A program without such spans gives nothing to
read."""
from benchmark.harness import say

LAYER = "scheduler and cache"
MOVES = "serve_tokens_per_s"


def read(run):
    cfg = run.cell.config
    kinds = list(cfg.get("layer_types_run", ()))
    rounds = [e["args"] for e in run.spans if e["name"] == "decode_round"
              and "state_rows" in e.get("args", ())
              and run.window_wall[0] <= e["ts"] / 1e6 <= run.window_wall[1]]
    if not rounds or "kda" not in kinds:
        return None
    # a head's state is float32; a latent row is the model's bfloat16
    state = 2 * cfg["num_attention_heads"] * cfg["head_dim"] ** 2 * 4
    latent = (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) * 2 \
        * kinds.count("mla")
    state_b = sum(int(r["state_rows"]) for r in rounds) * state
    latent_b = sum(int(r.get("latent_rows", 0)) for r in rounds) * latent
    backend = (run.counters.get("state") or {}).get("backend")
    say(f"cache bytes the window's {len(rounds)} rounds had to move: "
        f"{state_b / 1e9:.2f} GB of recurrent state, {latent_b / 1e9:.2f} "
        f"GB of latent rows; the recurrence ran on {backend!r}")
    return 100.0 * state_b / (state_b + latent_b)
