"""setup_s: seconds from process start to the opening of the window
(namespace load, warm-up traffic, kernel warm-up, schedule draw)."""


def read(ctx):
    return ctx.setup_s
