"""What the decode program holds on the device while it runs, from the
compiler's `memory_analysis()` as the program's runtime captured it
(`CostRecord` of label `generation_decode`): argument + output + temp -
alias bytes. The cache counts as an argument and again as an output
until the program donates it; this is the number that decided how many
slots load."""


def read(ctx):
    from paddle_tpu.monitor import cost_model

    rec = cost_model.latest_record("generation_decode")
    if rec is None or rec.partial:
        return None
    return (rec.argument_bytes + rec.output_bytes + rec.temp_bytes
            - rec.alias_bytes)
