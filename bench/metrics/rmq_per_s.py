"""RMQs answered correctly inside the window, per second of the window."""


def read(ctx):
    answered = sum(rec.queries for rec in ctx.records if rec.ok and rec.t_done <= ctx.t1)
    return max(answered - ctx.wrong_answers, 0) / (ctx.t1 - ctx.t0)
