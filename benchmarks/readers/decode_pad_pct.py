"""Share of the tokens the XLA decode attention reads that are padding: 100 x
(1 - live_token_steps / table_token_steps) over the window, both counted at the
call of the decode scan (live: the rows' context lengths; table: padded rows x
table width x block_tokens)."""


def read(ctx):
    d = ctx["reader"]("decode_rows_counted").delta
    live = d(ctx, "decode", "live_token_steps")
    table = d(ctx, "decode", "table_token_steps")
    return None if live is None or not table else 100.0 * (1.0 - live / table)
