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
    """The ``serve.qwen2-deck`` cell, from its files and
    ``BENCHMARK.json``, with a small qwen2-shaped model, a short deck of
    short prompts and a small characterization for the advisor; its
    arrival rate is the traffic file's."""
    cell = harness.load_cell("serve.qwen2-deck")
    cell.config = dict(cell.config, **SERVE_SIZES, advisor=dict(
        cell.config["advisor"], buffer_bytes=256 << 10, iters=2))
    cell.traffic = dict(cell.traffic, deck=[[32, 2], [64, 1]], batch=4,
                        new_tokens=8)
    return cell


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
