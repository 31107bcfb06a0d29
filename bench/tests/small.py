"""The benchmark's cells cut to sizes a CPU test can run: the same files,
drivers and checks, with small buffers, a small qwen2-shaped model and a
short deck.  Nothing here is a measurement."""
import dataclasses

from bench import harness

SERVE_SIZES = {"hidden_size": 1024, "intermediate_size": 2048,
               "num_hidden_layers": 2, "num_attention_heads": 4,
               "num_key_value_heads": 2, "head_dim": 256,
               "vocab_size": 8192}


def char_cell(traffic: str, sizes=(256 << 10,)) -> harness.Cell:
    """The characterization cell under the traffic mix ``traffic``."""
    cell = harness.load_cell("char.hbm-stream")
    cell.traffic = dict(harness.load_json("traffic", traffic),
                        buffer_bytes=list(sizes))
    cell.config = dict(cell.config, iters=2)
    return cell


def serve_cell() -> harness.Cell:
    """The qwen2 serving configuration under the ``qwen2-deck`` traffic
    mix, built from those files alone, with its end-to-end metrics."""
    config = harness.load_json("configs", "qwen2-1.5b")
    traffic = harness.load_json("traffic", "qwen2-deck")
    spec = {"name": "serve.qwen2-deck", "config": config["name"],
            "traffic": traffic["name"], "chips": 1}
    config = dict(config, **SERVE_SIZES, advisor=dict(
        config["advisor"], buffer_bytes=256 << 10, iters=2))
    traffic = dict(traffic, deck=[[32, 2], [64, 1]], batch=4, new_tokens=8)
    return harness.Cell(spec["name"], spec, config, traffic,
                        {"gen_tok_s": "tokens/s", "call_p90_ms": "ms",
                         "setup_s": "s"}, {})


def program_config(cfg: dict):
    """The program's qwen2 config at the sizes of ``cfg``."""
    from repro.configs.base import get_config
    return dataclasses.replace(
        get_config("qwen2-1.5b"), n_layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"])


def run(cell, monkeypatch, *, seed=3_000_000_019, seconds=1.0, trace=False,
        hook=None):
    """One run of ``cell`` on the CPU; ``hook(driver)`` breaks it."""
    from bench import qwen2_program
    monkeypatch.setattr(qwen2_program, "program_config", program_config)
    return harness.run_cell(cell, seed=seed, seconds=seconds, trace=trace,
                            on_chip=False, driver_hook=hook)
