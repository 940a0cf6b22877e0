"""Process start -> window start: loading, boot, warm-up, compilation."""


def read(cell, params):
    return cell.setup_s
