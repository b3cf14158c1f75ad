"""Imbalance of the routed experts held: the largest over the mean count of
token-assignments among them, per expert layer and step, averaged over the
window's steps and the layers (1.0 = even). From the step's own counter
`moe/assignments_held`, read once the window has closed."""


def read(ctx):
    held = ctx.get("assignments_held")      # [steps, layers, held]
    if ctx.get("job") != "train" or held is None or not len(held):
        return None
    ratios = [max(layer) / (sum(layer) / len(layer))
              for step in held for layer in step if sum(layer)]
    return sum(ratios) / len(ratios) if ratios else None
