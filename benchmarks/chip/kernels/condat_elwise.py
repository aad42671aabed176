"""Work of the fused Condat elementwise passes, fp32.

``condat_elwise_primal``: X_new = max(X - tau grad - tau Phi^T U, 0)
reads X, Phi^T U and grad and writes X_new; the low-rank solver also
writes the over-relaxed 2 X_new - X.  ``condat_elwise_dual``: clamp of
U + sig (2 Phi X_new - Phi X) to [-W, W] over every detail scale, with
one weight per stamp and scale."""


def primal(cell):
    px = cell.local_records * cell.sizes["stamp"] ** 2
    if cell.traffic["solver"]["mode"] == "lowrank":
        return 7 * px, 5 * 4 * px
    return 5 * px, 4 * 4 * px


def dual(cell):
    m = cell.local_records * cell.sizes["n_scales"]
    px = m * cell.sizes["stamp"] ** 2
    return 6 * px, 4 * 4 * px + 4 * m


KERNELS = {"condat_elwise_primal": primal, "condat_elwise_dual": dual}
