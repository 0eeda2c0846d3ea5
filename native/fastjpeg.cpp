// Threaded JPEG decode + crop + resize + flip + normalize (ctypes ABI).
//
// Round-4 verdict: the ResNet-50 input-fed bench is host-bound and the
// decode stage still ran in the tf.data graph while only normalize ran
// in native/fastdata.cpp. This library makes the
// whole per-image path ONE C++ stage on the existing thread-pool
// pattern: libjpeg(-turbo) decode (with DCT scaled decoding — 1/2, 1/4,
// 1/8 — whenever the crop region stays >= the output size, which cuts
// IDCT work up to 64x on large sources), the classic ResNet
// RandomResizedCrop / eval central-crop in ORIGINAL image coordinates,
// fused bilinear resize straight from the scaled crop window into the
// normalized float32 output. Randomness is a splitmix64 stream seeded
// PER IMAGE by the caller (exact-resume capable: seed = f(stream
// position)); the numpy mirror in data/imagenet.py reproduces the same
// draws bit-for-bit so parity is testable without hardware.
//
// ABI (see tensorflow_examples_tpu/native/__init__.py):
//   fj_decode_augment_batch : concatenated jpeg bytes -> f32 NHWC batch
//   fj_jpeg_dims            : header-only (h, w) probe
//
// Build: make -C native build/libfastjpeg.so   (links -ljpeg; the lib
// is optional — the Python side falls back to the tf.data decode path
// when it is absent, same degradation contract as libfastdata.)

#include <cmath>
#include <csetjmp>
#include <cstdint>
#include <cstdio>
#include <cstring>
#include <thread>
#include <vector>

#include <jpeglib.h>

namespace {

// ------------------------------------------------------------- threading

template <typename Fn>
void parallel_for(int64_t n, int threads, Fn fn) {
  if (threads <= 1 || n <= 1) {
    for (int64_t i = 0; i < n; ++i) fn(i);
    return;
  }
  std::vector<std::thread> pool;
  int64_t chunk = (n + threads - 1) / threads;
  for (int t = 0; t < threads; ++t) {
    int64_t lo = t * chunk, hi = lo + chunk < n ? lo + chunk : n;
    if (lo >= hi) break;
    pool.emplace_back([=] {
      for (int64_t i = lo; i < hi; ++i) fn(i);
    });
  }
  for (auto& th : pool) th.join();
}

// ------------------------------------------------------------------ rng
//
// splitmix64 — tiny, seedable, and trivially mirrored in Python ints
// (data/imagenet.py _SplitMix64). All uniforms are drawn as
// (x >> 11) * 2^-53 float64 so both sides agree bit-for-bit.

struct SplitMix64 {
  uint64_t s;
  explicit SplitMix64(uint64_t seed) : s(seed) {}
  uint64_t next() {
    s += 0x9E3779B97F4A7C15ULL;
    uint64_t z = s;
    z = (z ^ (z >> 30)) * 0xBF58476D1CE4E5B9ULL;
    z = (z ^ (z >> 27)) * 0x94D049BB133111EBULL;
    return z ^ (z >> 31);
  }
  double u01() { return (next() >> 11) * (1.0 / 9007199254740992.0); }
};

// ------------------------------------------------------------ jpeg glue

struct JpegErr {
  jpeg_error_mgr mgr;
  jmp_buf jb;
};

void jpeg_err_exit(j_common_ptr cinfo) {
  JpegErr* e = reinterpret_cast<JpegErr*>(cinfo->err);
  longjmp(e->jb, 1);
}

// Decoded window: `rgb` holds rows [oy0, oy0+h) x cols [ox0, ox0+w) of
// the 1/denom-scaled image (libjpeg may widen the column window to MCU
// boundaries, so ox0/w can cover more than requested).
struct Window {
  std::vector<uint8_t> rgb;
  int oy0 = 0, ox0 = 0, h = 0, w = 0;   // window placement, scaled coords
  int sh = 0, sw = 0;                   // full scaled image dims
};

// Decode only the scaled-coordinate window [wy0, wy0+wh) — the partial
// decode tf.image's decode_and_crop_jpeg uses, via libjpeg-turbo's
// jpeg_skip_scanlines / jpeg_crop_scanline — DCT-downscaled by
// 1/denom. Returns false on any libjpeg error (corrupt stream).
bool decode_window(const uint8_t* data, size_t len, int denom, int wy0,
                   int wh, int wx0, int ww, Window* win) {
  jpeg_decompress_struct cinfo;
  JpegErr jerr;
  cinfo.err = jpeg_std_error(&jerr.mgr);
  jerr.mgr.error_exit = jpeg_err_exit;
  if (setjmp(jerr.jb)) {
    jpeg_destroy_decompress(&cinfo);
    return false;
  }
  jpeg_create_decompress(&cinfo);
  jpeg_mem_src(&cinfo, const_cast<uint8_t*>(data),
               static_cast<unsigned long>(len));
  jpeg_read_header(&cinfo, TRUE);
  cinfo.out_color_space = JCS_RGB;
  cinfo.scale_num = 1;
  cinfo.scale_denom = static_cast<unsigned int>(denom);
  jpeg_start_decompress(&cinfo);
  const int sh = static_cast<int>(cinfo.output_height);
  const int sw = static_cast<int>(cinfo.output_width);
  // Clamp the request to the scaled frame.
  if (wy0 < 0) wy0 = 0;
  if (wx0 < 0) wx0 = 0;
  if (wy0 + wh > sh) wh = sh - wy0;
  if (wx0 + ww > sw) ww = sw - wx0;
  if (wh <= 0 || ww <= 0) {
    wy0 = wx0 = 0;
    wh = sh;
    ww = sw;
  }
  // Column crop first (may widen to an MCU boundary).
  JDIMENSION xoff = static_cast<JDIMENSION>(wx0);
  JDIMENSION xwidth = static_cast<JDIMENSION>(ww);
  if (!(xoff == 0 && xwidth == static_cast<JDIMENSION>(sw))) {
    jpeg_crop_scanline(&cinfo, &xoff, &xwidth);
  }
  if (wy0 > 0) {
    jpeg_skip_scanlines(&cinfo, static_cast<JDIMENSION>(wy0));
  }
  const int oy0 = static_cast<int>(cinfo.output_scanline);
  const int w = static_cast<int>(xwidth);
  win->rgb.resize(static_cast<size_t>(wh) * w * 3);
  int row = 0;
  while (row < wh && cinfo.output_scanline < cinfo.output_height) {
    JSAMPROW dst = win->rgb.data() + static_cast<size_t>(row) * w * 3;
    row += static_cast<int>(jpeg_read_scanlines(&cinfo, &dst, 1));
  }
  // Rows below the window are never decoded: abort, don't finish.
  jpeg_abort_decompress(&cinfo);
  jpeg_destroy_decompress(&cinfo);
  win->oy0 = oy0;
  win->ox0 = static_cast<int>(xoff);
  win->h = row;
  win->w = w;
  win->sh = sh;
  win->sw = sw;
  return row == wh;
}

// Header-only dimensions. Returns false on error.
bool jpeg_dims(const uint8_t* data, size_t len, int* h, int* w) {
  jpeg_decompress_struct cinfo;
  JpegErr jerr;
  cinfo.err = jpeg_std_error(&jerr.mgr);
  jerr.mgr.error_exit = jpeg_err_exit;
  if (setjmp(jerr.jb)) {
    jpeg_destroy_decompress(&cinfo);
    return false;
  }
  jpeg_create_decompress(&cinfo);
  jpeg_mem_src(&cinfo, const_cast<uint8_t*>(data),
               static_cast<unsigned long>(len));
  jpeg_read_header(&cinfo, TRUE);
  *h = static_cast<int>(cinfo.image_height);
  *w = static_cast<int>(cinfo.image_width);
  jpeg_destroy_decompress(&cinfo);
  return true;
}

// ---------------------------------------------------------- crop policy

struct Crop {
  int y0, x0, h, w;  // in ORIGINAL image coordinates
  bool flip;
};

// Draw order is the contract with the numpy mirror: per attempt
// (u_area, u_logratio), then on success (u_y, u_x); after the loop
// u_flip. Mirrors torchvision RandomResizedCrop semantics.
Crop train_crop(int H, int W, SplitMix64* rng) {
  const double log_lo = std::log(3.0 / 4.0), log_hi = std::log(4.0 / 3.0);
  Crop c{};
  bool found = false;
  for (int attempt = 0; attempt < 10 && !found; ++attempt) {
    double a_frac = 0.08 + rng->u01() * 0.92;
    double ratio = std::exp(log_lo + rng->u01() * (log_hi - log_lo));
    double area = a_frac * H * W;
    int w = static_cast<int>(std::floor(std::sqrt(area * ratio) + 0.5));
    int h = static_cast<int>(std::floor(std::sqrt(area / ratio) + 0.5));
    if (w >= 1 && h >= 1 && w <= W && h <= H) {
      c.y0 = static_cast<int>(std::floor(rng->u01() * (H - h + 1)));
      c.x0 = static_cast<int>(std::floor(rng->u01() * (W - w + 1)));
      c.h = h;
      c.w = w;
      found = true;
    }
  }
  if (!found) {  // fallback: central min-square (matches the mirror)
    int m = H < W ? H : W;
    c.h = c.w = m;
    c.y0 = (H - m) / 2;
    c.x0 = (W - m) / 2;
  }
  c.flip = rng->u01() < 0.5;
  return c;
}

Crop eval_crop(int H, int W) {
  int m = H < W ? H : W;
  int crop = static_cast<int>(0.875 * m);
  if (crop < 1) crop = 1;
  return Crop{(H - crop) / 2, (W - crop) / 2, crop, crop, false};
}

// Largest DCT denom in {8,4,2,1} that keeps the scaled crop >= out so
// the bilinear stage only ever downsamples.
int pick_denom(const Crop& c, int out) {
  for (int d : {8, 4, 2}) {
    if (c.h / d >= out && c.w / d >= out) return d;
  }
  return 1;
}

// Bilinear-sample the crop (original coords) from a decoded window of
// the 1/denom-scaled image, flip, normalize, write [out, out, 3]
// floats. Sample indices are computed in scaled-IMAGE coordinates
// (identical to the full-frame formulation, so the numpy mirror holds)
// and only then rebased into the window, whose one-pixel margin covers
// the bilinear neighbors; clamping against the window edge equals
// frame-edge clamping because the window is clamped to the frame.
void resize_normalize(const Window& win, int denom, const Crop& c, int out,
                      const float* mean, const float* inv_std, float* dst) {
  const double inv_d = 1.0 / denom;
  const int sh = win.sh, sw = win.sw;
  auto rebase_y = [&](int y) {
    y -= win.oy0;
    if (y < 0) y = 0;
    if (y >= win.h) y = win.h - 1;
    return y;
  };
  auto rebase_x = [&](int x) {
    x -= win.ox0;
    if (x < 0) x = 0;
    if (x >= win.w) x = win.w - 1;
    return x;
  };
  for (int oy = 0; oy < out; ++oy) {
    // Original-coordinate sample center (half-pixel convention), then
    // mapped into the scaled image's pixel grid.
    double sy = c.y0 + (oy + 0.5) * c.h / out - 0.5;
    double sys = (sy + 0.5) * inv_d - 0.5;
    int y1 = static_cast<int>(std::floor(sys));
    double fy = sys - y1;
    int y2 = y1 + 1;
    if (y1 < 0) y1 = 0;
    if (y2 < 0) y2 = 0;
    if (y1 >= sh) y1 = sh - 1;
    if (y2 >= sh) y2 = sh - 1;
    int by1 = rebase_y(y1), by2 = rebase_y(y2);
    for (int ox = 0; ox < out; ++ox) {
      int ox_dst = c.flip ? (out - 1 - ox) : ox;
      double sx = c.x0 + (ox + 0.5) * c.w / out - 0.5;
      double sxs = (sx + 0.5) * inv_d - 0.5;
      int x1 = static_cast<int>(std::floor(sxs));
      double fx = sxs - x1;
      int x2 = x1 + 1;
      if (x1 < 0) x1 = 0;
      if (x2 < 0) x2 = 0;
      if (x1 >= sw) x1 = sw - 1;
      if (x2 >= sw) x2 = sw - 1;
      int bx1 = rebase_x(x1), bx2 = rebase_x(x2);
      const uint8_t* base = win.rgb.data();
      const uint8_t* p11 = base + (static_cast<size_t>(by1) * win.w + bx1) * 3;
      const uint8_t* p12 = base + (static_cast<size_t>(by1) * win.w + bx2) * 3;
      const uint8_t* p21 = base + (static_cast<size_t>(by2) * win.w + bx1) * 3;
      const uint8_t* p22 = base + (static_cast<size_t>(by2) * win.w + bx2) * 3;
      float* q = dst + (static_cast<size_t>(oy) * out + ox_dst) * 3;
      for (int k = 0; k < 3; ++k) {
        double v = (1 - fy) * ((1 - fx) * p11[k] + fx * p12[k]) +
                   fy * ((1 - fx) * p21[k] + fx * p22[k]);
        q[k] = (static_cast<float>(v) * (1.0f / 255.0f) - mean[k]) *
               inv_std[k];
      }
    }
  }
}

}  // namespace

extern "C" {

// Returns the number of FAILED decodes (0 == all good). Failed images
// get ok_flags[i] = 0 and a zeroed output slot; callers decide whether
// to drop or substitute.
int64_t fj_decode_augment_batch(const uint8_t* data, const int64_t* offsets,
                                int64_t n, int32_t train, int32_t out_size,
                                const uint64_t* seeds, const float* mean,
                                const float* inv_std, float* out,
                                int64_t threads, uint8_t* ok_flags) {
  std::vector<int64_t> failures(n > 0 ? n : 1, 0);
  parallel_for(n, static_cast<int>(threads), [&](int64_t i) {
    const uint8_t* img = data + offsets[i];
    size_t len = static_cast<size_t>(offsets[i + 1] - offsets[i]);
    float* dst =
        out + static_cast<size_t>(i) * out_size * out_size * 3;
    int H = 0, W = 0;
    Crop c;
    if (!jpeg_dims(img, len, &H, &W) || H < 1 || W < 1) {
      std::memset(dst, 0, sizeof(float) * out_size * out_size * 3);
      ok_flags[i] = 0;
      failures[i] = 1;
      return;
    }
    if (train) {
      SplitMix64 rng(seeds[i]);
      c = train_crop(H, W, &rng);
    } else {
      c = eval_crop(H, W);
    }
    int denom = pick_denom(c, out_size);
    // Scaled-coordinate window covering the crop plus a one-pixel
    // bilinear margin; decode_window clamps it to the frame.
    int wy0 = c.y0 / denom - 1;
    int wh = (c.y0 + c.h + denom - 1) / denom - wy0 + 2;
    int wx0 = c.x0 / denom - 1;
    int ww = (c.x0 + c.w + denom - 1) / denom - wx0 + 2;
    Window win;
    if (!decode_window(img, len, denom, wy0, wh, wx0, ww, &win)) {
      std::memset(dst, 0, sizeof(float) * out_size * out_size * 3);
      ok_flags[i] = 0;
      failures[i] = 1;
      return;
    }
    resize_normalize(win, denom, c, out_size, mean, inv_std, dst);
    ok_flags[i] = 1;
  });
  int64_t total = 0;
  for (int64_t i = 0; i < n; ++i) total += failures[i];
  return total;
}

int32_t fj_jpeg_dims(const uint8_t* data, int64_t len, int32_t* h,
                     int32_t* w) {
  int hh = 0, ww = 0;
  if (!jpeg_dims(data, static_cast<size_t>(len), &hh, &ww)) return 1;
  *h = hh;
  *w = ww;
  return 0;
}

}  // extern "C"
