"""Work of one ``starlet2d_smooth`` execution: one B3 smoothing (a
5-tap pass along each image axis) of every stamp on the chip, read
once and written once in fp32."""


def smooth(cell):
    n = cell.local_records
    px = n * cell.sizes["stamp"] ** 2
    return 2 * 5 * 2 * px, 2 * 4 * px      # 5 multiply-adds per axis


KERNELS = {"starlet2d_smooth": smooth}
