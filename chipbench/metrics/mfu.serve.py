"""The whole serving step's share of the chip's bf16 peak: the forward
operations every prompt and output token in the window needs
(``lib/work.py`` over the cell's architecture module: matrices, causal
attention at each token's context, the LM head once per produced token),
over the window, over the peak."""
from chipbench.lib import work

UNIT = "%"


def read(run):
    rec = run.record
    arch, conf, stats = rec["arch"], rec["conf"], rec["stats"]
    t0, t1 = rec["window"]
    flops = 0.0
    for i in stats.first_tokens:
        flops += work.prompt_flops(arch, conf, rec["prompt_lens"][i])
    for i, times in enumerate(rec["record"].times):
        S = rec["prompt_lens"][i]
        for k, t in enumerate(times[1:], start=1):
            if t0 < t <= t1:
                # the k-th decoded token attends the prompt, the k tokens
                # before it and itself
                flops += work.decode_flops(arch, conf, S + k)
    if flops <= 0:
        return None
    return 100.0 * flops / stats.window_s / run.peak["bf16_flops_per_s"]
