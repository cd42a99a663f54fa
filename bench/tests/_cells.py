"""A cell of ``BENCHMARK.json`` cut to a size the CPU runs in seconds:
the same files, the same harness, smaller numbers."""

from bench import harness

SMALL_MODEL = dict(num_hidden_layers=2, hidden_size=256,
                   num_attention_heads=4, num_key_value_heads=2,
                   intermediate_size=512, vocab_size=512, vocab_padded=2048)


def reduced_cell(name: str) -> harness.Cell:
    cell = harness.load_cell(name)
    cell.conf.update(SMALL_MODEL)
    cell.conf["engine"].update(max_slots=4, cache_len=256)
    t = cell.traffic
    if t["loop"] == "open":
        t.update(rate_per_s=4.0,
                 prompt_tokens=dict(t["prompt_tokens"], median=40, min=8,
                                    max=150),
                 output_tokens=dict(t["output_tokens"], median=8, min=2,
                                    max=20))
    else:
        t.update(clients=4,
                 prompt_tokens=dict(t["prompt_tokens"], min=60, max=100),
                 output_tokens=dict(t["output_tokens"], min=20, max=60))
    return cell
