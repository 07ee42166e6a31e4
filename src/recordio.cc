// Native IO library: RecordIO + multithreaded image decode pipeline.
//
// Ref: 3rdparty/dmlc-core recordio (format: [magic u32][lrec u32][data]
// [pad4], magic 0xced7230a) and src/io/iter_image_recordio_2.cc (N decode
// threads -> batch queue -> prefetch).  This is the TPU build's native
// data-loader: workers pread records, parse IRHeader, decode JPEG via
// libjpeg, resize/crop/mirror/normalize into pinned batch buffers that
// Python hands to PjRt host-to-device transfer.
//
// Exposed as a flat C ABI (ref: the c_api boundary) consumed via ctypes.

#include <csetjmp>
#include <cstddef>
#include <cstdio>

#include <jpeglib.h>

#include <unistd.h>

#include <algorithm>
#include <atomic>
#include <cmath>
#include <condition_variable>
#include <cstdint>
#include <cstdio>
#include <cstring>
#include <map>
#include <mutex>
#include <queue>
#include <random>
#include <string>
#include <thread>
#include <vector>

namespace {

constexpr uint32_t kMagic = 0xced7230a;
constexpr uint32_t kLenMask = (1u << 29) - 1;

// ---------------------------------------------------------------------------
// RecordIO

struct RecordWriter {
  FILE* f = nullptr;
};

struct RecordReader {
  FILE* f = nullptr;
  std::vector<char> buf;
};

// IRHeader (ref: mx.recordio.IRHeader): flag u32, label f32, id u64, id2 u64
struct IRHeader {
  uint32_t flag;
  float label;
  uint64_t id;
  uint64_t id2;
};

// ---------------------------------------------------------------------------
// JPEG decode via libjpeg

// libjpeg's default error_exit calls exit(); corrupt records must decode
// as a failure return instead, so route fatal errors through longjmp (the
// canonical libjpeg.txt recovery pattern).
struct JpegErrorJmp {
  jpeg_error_mgr pub;
  jmp_buf jb;
};

extern "C" void MxtpuJpegErrorExit(j_common_ptr cinfo) {
  JpegErrorJmp* e = reinterpret_cast<JpegErrorJmp*>(cinfo->err);
  longjmp(e->jb, 1);
}

extern "C" void MxtpuJpegSilence(j_common_ptr, int) {}

bool DecodeJpeg(const uint8_t* data, size_t len, std::vector<uint8_t>* out,
                int* w, int* h, int* channels, bool gray) {
  if (len < 2 || data[0] != 0xFF || data[1] != 0xD8) return false;
  jpeg_decompress_struct cinfo;
  JpegErrorJmp jerr;
  cinfo.err = jpeg_std_error(&jerr.pub);
  jerr.pub.error_exit = MxtpuJpegErrorExit;
  jerr.pub.emit_message = MxtpuJpegSilence;  // no warning spam on stderr
  if (setjmp(jerr.jb)) {
    jpeg_destroy_decompress(&cinfo);
    return false;
  }
  jpeg_create_decompress(&cinfo);
  jpeg_mem_src(&cinfo, data, len);
  if (jpeg_read_header(&cinfo, TRUE) != JPEG_HEADER_OK) {
    jpeg_destroy_decompress(&cinfo);
    return false;
  }
  cinfo.out_color_space = gray ? JCS_GRAYSCALE : JCS_RGB;
  jpeg_start_decompress(&cinfo);
  *w = cinfo.output_width;
  *h = cinfo.output_height;
  *channels = cinfo.output_components;
  out->resize(static_cast<size_t>(*w) * (*h) * (*channels));
  while (cinfo.output_scanline < cinfo.output_height) {
    uint8_t* row = out->data() +
        static_cast<size_t>(cinfo.output_scanline) * (*w) * (*channels);
    jpeg_read_scanlines(&cinfo, &row, 1);
  }
  jpeg_finish_decompress(&cinfo);
  jpeg_destroy_decompress(&cinfo);
  return true;
}

// nearest-neighbour resize HWC uint8 (inter_method 0)
void ResizeNearest(const uint8_t* src, int sw, int sh, int c,
                   uint8_t* dst, int dw, int dh) {
  const float sx = static_cast<float>(sw) / dw;
  const float sy = static_cast<float>(sh) / dh;
  for (int y = 0; y < dh; ++y) {
    int yy = std::min(sh - 1, static_cast<int>((y + 0.5f) * sy));
    for (int x = 0; x < dw; ++x) {
      int xx = std::min(sw - 1, static_cast<int>((x + 0.5f) * sx));
      for (int ch = 0; ch < c; ++ch) {
        dst[(y * dw + x) * c + ch] = src[(yy * sw + xx) * c + ch];
      }
    }
  }
}

// bilinear resize HWC uint8
void ResizeBilinear(const uint8_t* src, int sw, int sh, int c,
                    uint8_t* dst, int dw, int dh) {
  const float sx = static_cast<float>(sw) / dw;
  const float sy = static_cast<float>(sh) / dh;
  for (int y = 0; y < dh; ++y) {
    float fy = (y + 0.5f) * sy - 0.5f;
    int y0 = std::max(0, static_cast<int>(fy));
    int y1 = std::min(sh - 1, y0 + 1);
    float wy = fy - y0;
    for (int x = 0; x < dw; ++x) {
      float fx = (x + 0.5f) * sx - 0.5f;
      int x0 = std::max(0, static_cast<int>(fx));
      int x1 = std::min(sw - 1, x0 + 1);
      float wx = fx - x0;
      for (int ch = 0; ch < c; ++ch) {
        float v00 = src[(y0 * sw + x0) * c + ch];
        float v01 = src[(y0 * sw + x1) * c + ch];
        float v10 = src[(y1 * sw + x0) * c + ch];
        float v11 = src[(y1 * sw + x1) * c + ch];
        float v = v00 * (1 - wy) * (1 - wx) + v01 * (1 - wy) * wx +
                  v10 * wy * (1 - wx) + v11 * wy * wx;
        dst[(y * dw + x) * c + ch] = static_cast<uint8_t>(v + 0.5f);
      }
    }
  }
}

// ---------------------------------------------------------------------------
// Image pipeline: threaded decode + augment + batch assembly

struct PipelineConfig {
  int c, h, w;
  int batch_size;
  int num_threads;
  int shuffle, rand_crop, rand_mirror;
  int resize_short;  // <=0: disabled
  float mean[3], std_[3];
  uint64_t seed;
  // augmentation tier (ref: src/io/image_aug_default.cc):
  int random_resized_crop = 0;      // area/aspect-sampled crop
  float min_area = 1.f, max_area = 1.f;        // fraction of source
  float min_aspect = 1.f, max_aspect = 1.f;    // w/h ratio range
  float brightness = 0.f, contrast = 0.f, saturation = 0.f;
  float hue_deg = 0.f;              // max |hue shift|, OpenCV half-deg
  int inter_method = 1;             // 0 nearest, 1 bilinear, 9/10 random
};

void Resize(const uint8_t* src, int sw, int sh, int c, uint8_t* dst,
            int dw, int dh, int method) {
  if (method == 0) {
    ResizeNearest(src, sw, sh, c, dst, dw, dh);
  } else {
    ResizeBilinear(src, sw, sh, c, dst, dw, dh);
  }
}

struct Batch {
  std::vector<float> data;
  std::vector<float> labels;
  int count = 0;
};

struct ImagePipeline {
  FILE* f = nullptr;
  std::vector<uint64_t> offsets;
  std::vector<float> labels_at;  // parsed lazily; offsets drive reads
  PipelineConfig cfg;
  std::vector<size_t> order;
  std::atomic<size_t> cursor{0};
  size_t num_batches = 0;

  std::vector<std::thread> workers;
  // completed batches keyed by batch index: the consumer emits them in
  // sequence order regardless of which worker finished first (the
  // reference's batcher/prefetcher preserves record order; without
  // this, batch order silently depends on thread scheduling — a race
  // caught by the parity test under CPU load)
  std::map<size_t, Batch*> ready;
  size_t next_emit = 0;  // guarded by mu
  std::mutex mu;
  std::condition_variable cv_ready, cv_space;
  size_t max_queue = 4;
  std::atomic<bool> stop{false};
  std::atomic<int> active_workers{0};
  // records each worker has decoded since the pipeline was made;
  // guarded by mu
  std::vector<uint64_t> decoded_by;
  uint64_t epoch_seed;

  ~ImagePipeline() { Shutdown(); }

  void Shutdown() {
    {
      // stop must flip under mu: a worker that just evaluated the
      // cv_space predicate false would otherwise sleep through this
      // notify and hang the join (lost wakeup)
      std::lock_guard<std::mutex> lk(mu);
      stop.store(true);
    }
    cv_space.notify_all();
    cv_ready.notify_all();
    for (auto& t : workers) {
      if (t.joinable()) t.join();
    }
    workers.clear();
    std::lock_guard<std::mutex> lk(mu);
    for (auto& kv : ready) delete kv.second;
    ready.clear();
    if (f) {
      fclose(f);
      f = nullptr;
    }
  }

  bool ReadRecordAt(uint64_t off, std::vector<char>* buf) {
    // thread-safe independent reads via pread on the raw fd.
    // cflag continuation chunks (dmlc magic-escape splitting) are
    // reassembled with the removed magic word re-inserted.
    int fd = fileno(f);
    buf->clear();
    bool first = true;
    while (true) {
      uint32_t hdr[2];
      if (pread(fd, hdr, 8, off) != 8) return false;
      if (hdr[0] != kMagic) return false;
      uint32_t len = hdr[1] & kLenMask;
      uint32_t cflag = hdr[1] >> 29;
      if (first && cflag != 0 && cflag != 1) return false;
      if (!first) {
        if (cflag != 2 && cflag != 3) return false;
        uint32_t magic_word = kMagic;
        const char* m = reinterpret_cast<const char*>(&magic_word);
        buf->insert(buf->end(), m, m + 4);
      }
      size_t base = buf->size();
      buf->resize(base + len);
      if (pread(fd, buf->data() + base, len, off + 8) !=
          static_cast<ssize_t>(len)) {
        return false;
      }
      if (cflag == 0 || cflag == 3) return true;
      off += 8 + len + ((4 - len % 4) % 4);
      first = false;
    }
  }

  void DecodeOne(const std::vector<char>& rec, float* out, float* label,
                 std::mt19937* rng) {
    const char* p = rec.data();
    IRHeader h;
    // h.flag comes from the file: a truncated/corrupt record can carry a
    // flag whose label vector extends past the payload, so bound-check
    // before the label read and the skip arithmetic (size_t underflow).
    if (rec.size() < sizeof(h)) {
      *label = 0.f;
      std::fill(out, out + static_cast<size_t>(cfg.c) * cfg.h * cfg.w, 0.f);
      return;
    }
    std::memcpy(&h, p, sizeof(h));
    // flag > 0 means the label is a packed float vector of that many
    // elements preceding the image bytes (ref: mx.recordio.unpack strips
    // for flag > 0 — size-1 vectors included)
    size_t skip = sizeof(h) + (h.flag > 0 ? 4ull * h.flag : 0ull);
    if (skip > rec.size()) {
      *label = 0.f;
      std::fill(out, out + static_cast<size_t>(cfg.c) * cfg.h * cfg.w, 0.f);
      return;
    }
    float lab;
    if (h.flag > 0) {
      std::memcpy(&lab, p + sizeof(h), 4);  // first element of the vector
    } else {
      lab = h.label;
    }
    *label = lab;
    const uint8_t* img = reinterpret_cast<const uint8_t*>(p + skip);
    size_t img_len = rec.size() - skip;

    std::vector<uint8_t> pixels;
    int w = 0, hh = 0, ch = 0;
    if (!DecodeJpeg(img, img_len, &pixels, &w, &hh, &ch, cfg.c == 1)) {
      std::fill(out, out + static_cast<size_t>(cfg.c) * cfg.h * cfg.w, 0.f);
      return;
    }
    std::uniform_real_distribution<float> u01(0.f, 1.f);
    int inter = cfg.inter_method;
    if (inter == 9 || inter == 10) inter = ((*rng)() & 1) ? 1 : 0;

    std::vector<uint8_t> resized;
    int x0 = 0, y0 = 0;
    if (cfg.random_resized_crop) {
      // area/aspect-sampled crop, resized to the target (ref:
      // image_aug_default.cc max_random_area/max_aspect_ratio path)
      int cw = -1, chh = -1;
      for (int attempt = 0; attempt < 10 && cw < 0; ++attempt) {
        float area = (cfg.min_area +
                      u01(*rng) * (cfg.max_area - cfg.min_area)) *
                     static_cast<float>(w) * hh;
        float la = std::log(cfg.min_aspect), lb = std::log(cfg.max_aspect);
        float ar = std::exp(la + u01(*rng) * (lb - la));
        int tw = static_cast<int>(std::sqrt(area * ar) + 0.5f);
        int th = static_cast<int>(std::sqrt(area / ar) + 0.5f);
        if (tw > 0 && th > 0 && tw <= w && th <= hh) {
          cw = tw;
          chh = th;
        }
      }
      if (cw < 0) {  // fallback: largest centered square
        cw = chh = std::min(w, hh);
      }
      x0 = (w == cw) ? 0 : static_cast<int>((*rng)() % (w - cw + 1));
      y0 = (hh == chh) ? 0 : static_cast<int>((*rng)() % (hh - chh + 1));
      std::vector<uint8_t> crop(static_cast<size_t>(cw) * chh * ch);
      for (int y = 0; y < chh; ++y) {
        std::memcpy(crop.data() + static_cast<size_t>(y) * cw * ch,
                    pixels.data() +
                        (static_cast<size_t>(y0 + y) * w + x0) * ch,
                    static_cast<size_t>(cw) * ch);
      }
      resized.resize(static_cast<size_t>(cfg.w) * cfg.h * ch);
      Resize(crop.data(), cw, chh, ch, resized.data(), cfg.w, cfg.h,
             inter);
      pixels.swap(resized);
      w = cfg.w;
      hh = cfg.h;
      x0 = y0 = 0;
    } else {
      // resize shorter side
      if (cfg.resize_short > 0) {
        int shorter = std::min(w, hh);
        float scale = static_cast<float>(cfg.resize_short) / shorter;
        int nw = std::max(cfg.w, static_cast<int>(w * scale + 0.5f));
        int nh = std::max(cfg.h, static_cast<int>(hh * scale + 0.5f));
        resized.resize(static_cast<size_t>(nw) * nh * ch);
        Resize(pixels.data(), w, hh, ch, resized.data(), nw, nh, inter);
        pixels.swap(resized);
        w = nw;
        hh = nh;
      }
      if (w < cfg.w || hh < cfg.h) {
        int nw = std::max(w, cfg.w), nh = std::max(hh, cfg.h);
        resized.resize(static_cast<size_t>(nw) * nh * ch);
        Resize(pixels.data(), w, hh, ch, resized.data(), nw, nh, inter);
        pixels.swap(resized);
        w = nw;
        hh = nh;
      }
      if (cfg.rand_crop) {
        x0 = static_cast<int>((*rng)() % (w - cfg.w + 1));
        y0 = static_cast<int>((*rng)() % (hh - cfg.h + 1));
      } else {
        x0 = (w - cfg.w) / 2;
        y0 = (hh - cfg.h) / 2;
      }
    }
    bool mirror = cfg.rand_mirror && ((*rng)() & 1);

    // color jitter as ONE per-image 3x3 matrix + offset (brightness →
    // contrast → saturation → hue composed; saturation/hue preserve the
    // gray axis so only contrast contributes an offset).  Applied in
    // float during the normalize pass — no extra image-sized buffer.
    bool jitter = ch == 3 &&
                  (cfg.brightness > 0.f || cfg.contrast > 0.f ||
                   cfg.saturation > 0.f || cfg.hue_deg > 0.f);
    bool use_hue = cfg.hue_deg > 0.f;
    float M[3][3] = {{1, 0, 0}, {0, 1, 0}, {0, 0, 1}};
    float off = 0.f;
    if (jitter) {
      auto uj = [&](float j) {
        return 1.f + (2.f * u01(*rng) - 1.f) * j;
      };
      float ab = cfg.brightness > 0.f ? uj(cfg.brightness) : 1.f;
      float ac = cfg.contrast > 0.f ? uj(cfg.contrast) : 1.f;
      float as = cfg.saturation > 0.f ? uj(cfg.saturation) : 1.f;
      const float gw[3] = {0.299f, 0.587f, 0.114f};
      if (ac != 1.f) {
        double gsum = 0;
        for (int y = 0; y < cfg.h; ++y) {
          for (int x = 0; x < cfg.w; ++x) {
            const uint8_t* p = pixels.data() +
                ((static_cast<size_t>(y0 + y) * w) + x0 + x) * 3;
            gsum += gw[0] * p[0] + gw[1] * p[1] + gw[2] * p[2];
          }
        }
        float gray0 = static_cast<float>(
            gsum / (static_cast<double>(cfg.h) * cfg.w));
        off = (1.f - ac) * ab * gray0;
      }
      // S = as*I + (1-as) * 1 * gw^T   (rows identical in the 2nd term)
      float S[3][3];
      for (int r = 0; r < 3; ++r) {
        for (int col = 0; col < 3; ++col) {
          S[r][col] = (r == col ? as : 0.f) + (1.f - as) * gw[col];
        }
      }
      if (use_hue) {
        // hue rotation about the gray axis (YIQ approximation; the
        // reference's HSL conversion is per-pixel — same capability,
        // cheaper math).  hue_deg is in OpenCV half-degrees (max 180).
        // Skipped entirely at hue_deg=0: the YIQ constants don't
        // round-trip exactly and would bias channels at theta=0.
        float theta = (2.f * u01(*rng) - 1.f) * cfg.hue_deg / 180.f *
                      3.14159265f;
        float cs = std::cos(theta), sn = std::sin(theta);
        const float H[3][3] = {
            {0.299f + 0.701f * cs + 0.168f * sn,
             0.587f - 0.587f * cs + 0.330f * sn,
             0.114f - 0.114f * cs - 0.497f * sn},
            {0.299f - 0.299f * cs - 0.328f * sn,
             0.587f + 0.413f * cs + 0.035f * sn,
             0.114f - 0.114f * cs + 0.292f * sn},
            {0.299f - 0.300f * cs + 1.25f * sn,
             0.587f - 0.588f * cs - 1.05f * sn,
             0.114f + 0.886f * cs - 0.203f * sn}};
        // M = H * S * (ab*ac)
        for (int r = 0; r < 3; ++r) {
          for (int col = 0; col < 3; ++col) {
            M[r][col] = 0.f;
            for (int k = 0; k < 3; ++k) M[r][col] += H[r][k] * S[k][col];
            M[r][col] *= ab * ac;
          }
        }
      } else {
        for (int r = 0; r < 3; ++r) {
          for (int col = 0; col < 3; ++col) {
            M[r][col] = S[r][col] * ab * ac;
          }
        }
      }
    }

    // HWC crop -> CHW normalized (jitter matrix fused in)
    for (int cc = 0; cc < cfg.c; ++cc) {
      float m = cfg.mean[cc < 3 ? cc : 0];
      float s = cfg.std_[cc < 3 ? cc : 0];
      float* dst = out + static_cast<size_t>(cc) * cfg.h * cfg.w;
      for (int y = 0; y < cfg.h; ++y) {
        for (int x = 0; x < cfg.w; ++x) {
          int sx = mirror ? (cfg.w - 1 - x) : x;
          const uint8_t* p =
              pixels.data() +
              (static_cast<size_t>(y0 + y) * w + (x0 + sx)) * ch;
          float v;
          if (jitter) {
            v = M[cc][0] * p[0] + M[cc][1] * p[1] + M[cc][2] * p[2] + off;
            v = std::min(255.f, std::max(0.f, v));
          } else {
            v = static_cast<float>(p[ch == 1 ? 0 : cc]);
          }
          dst[y * cfg.w + x] = (v - m) / s;
        }
      }
    }
  }

  void WorkerLoop(int tid) {
    std::mt19937 rng(epoch_seed + 0x9e3779b9u * tid);
    const size_t bs = cfg.batch_size;
    while (!stop.load()) {
      size_t b = cursor.fetch_add(1);
      if (b >= num_batches) break;
      {
        // bounded lookahead: claim-order is sequential, so gating on
        // consumption progress bounds in-flight batches without the
        // full-queue deadlock an admission gate would have
        std::unique_lock<std::mutex> lk(mu);
        cv_space.wait(lk,
                      [&] { return b < next_emit + max_queue || stop; });
        if (stop) break;
      }
      auto* batch = new Batch;
      batch->data.resize(bs * cfg.c * cfg.h * cfg.w);
      batch->labels.resize(bs);
      batch->count = static_cast<int>(bs);
      std::vector<char> rec;
      for (size_t i = 0; i < bs; ++i) {
        size_t idx = order[b * bs + i];
        if (!ReadRecordAt(offsets[idx], &rec)) {
          batch->labels[i] = -1.f;
          continue;
        }
        DecodeOne(rec, batch->data.data() +
                       i * static_cast<size_t>(cfg.c) * cfg.h * cfg.w,
                  &batch->labels[i], &rng);
      }
      std::unique_lock<std::mutex> lk(mu);
      if (stop) {
        delete batch;
        break;
      }
      ready[b] = batch;
      decoded_by[tid] += bs;
      cv_ready.notify_all();
    }
    if (active_workers.fetch_sub(1) == 1) cv_ready.notify_all();
  }

  void Start() {
    stop.store(false);
    cursor.store(0);
    active_workers.store(cfg.num_threads);
    decoded_by.resize(cfg.num_threads, 0);
    for (int t = 0; t < cfg.num_threads; ++t) {
      workers.emplace_back(&ImagePipeline::WorkerLoop, this, t);
    }
  }
};

}  // namespace

// ---------------------------------------------------------------------------
// C ABI

extern "C" {

// ---- RecordIO writer ----
void* MXTPURecordIOWriterCreate(const char* path) {
  auto* w = new RecordWriter;
  w->f = fopen(path, "wb");
  if (!w->f) {
    delete w;
    return nullptr;
  }
  return w;
}

static bool WriteChunk(FILE* f, const char* data, uint64_t len,
                       uint32_t cflag) {
  if (len > kLenMask) return false;
  uint32_t hdr[2] = {kMagic,
                     (cflag << 29) | static_cast<uint32_t>(len)};
  if (fwrite(hdr, 1, 8, f) != 8) return false;
  if (len && fwrite(data, 1, len, f) != len) return false;
  static const char pad[4] = {0, 0, 0, 0};
  size_t p = (4 - len % 4) % 4;
  if (p && fwrite(pad, 1, p, f) != p) return false;
  return true;
}

int64_t MXTPURecordIOWrite(void* handle, const char* buf, uint64_t len) {
  auto* w = static_cast<RecordWriter*>(handle);
  int64_t pos = ftell(w->f);
  // dmlc magic-escape splitting, mirroring the python writer: split at
  // every 4-byte-aligned magic occurrence in the payload
  std::vector<uint64_t> splits;
  for (uint64_t i = 0; i + 4 <= len; i += 4) {
    uint32_t word;
    std::memcpy(&word, buf + i, 4);
    if (word == kMagic) splits.push_back(i);
  }
  if (splits.empty()) {
    if (len > kLenMask) return -1;
    if (!WriteChunk(w->f, buf, len, 0)) return -1;
    return pos;
  }
  // validate every chunk before writing anything
  uint64_t prev = 0;
  for (size_t i = 0; i <= splits.size(); ++i) {
    uint64_t end = (i < splits.size()) ? splits[i] : len;
    if (end - prev > kLenMask) return -1;
    prev = (i < splits.size()) ? splits[i] + 4 : end;
  }
  prev = 0;
  for (size_t i = 0; i <= splits.size(); ++i) {
    uint64_t end = (i < splits.size()) ? splits[i] : len;
    uint32_t flag = (i == 0) ? 1u : (i == splits.size() ? 3u : 2u);
    if (!WriteChunk(w->f, buf + prev, end - prev, flag)) return -1;
    prev = (i < splits.size()) ? splits[i] + 4 : end;
  }
  return pos;
}

void MXTPURecordIOWriterFree(void* handle) {
  auto* w = static_cast<RecordWriter*>(handle);
  if (w->f) fclose(w->f);
  delete w;
}

// ---- RecordIO reader ----
void* MXTPURecordIOReaderCreate(const char* path) {
  auto* r = new RecordReader;
  r->f = fopen(path, "rb");
  if (!r->f) {
    delete r;
    return nullptr;
  }
  return r;
}

// returns length, 0 on EOF, -1 on error; data pointer valid until next call
int64_t MXTPURecordIORead(void* handle, const char** out) {
  auto* r = static_cast<RecordReader*>(handle);
  r->buf.clear();
  bool first = true;
  while (true) {
    uint32_t hdr[2];
    if (fread(hdr, 1, 8, r->f) != 8) return first ? 0 : -1;
    if (hdr[0] != kMagic) return -1;
    uint32_t len = hdr[1] & kLenMask;
    uint32_t cflag = hdr[1] >> 29;
    if (first && cflag != 0 && cflag != 1) return -1;
    if (!first) {
      if (cflag != 2 && cflag != 3) return -1;
      // re-insert the magic word the writer removed at the split
      uint32_t magic_word = kMagic;
      const char* m = reinterpret_cast<const char*>(&magic_word);
      r->buf.insert(r->buf.end(), m, m + 4);
    }
    size_t base = r->buf.size();
    r->buf.resize(base + len);
    if (fread(r->buf.data() + base, 1, len, r->f) != len) return -1;
    size_t p = (4 - len % 4) % 4;
    if (p) fseek(r->f, static_cast<long>(p), SEEK_CUR);
    if (cflag == 0 || cflag == 3) {
      *out = r->buf.data();
      return static_cast<int64_t>(r->buf.size());
    }
    first = false;
  }
}

void MXTPURecordIOSeek(void* handle, uint64_t pos) {
  fseek(static_cast<RecordReader*>(handle)->f, static_cast<long>(pos),
        SEEK_SET);
}

int64_t MXTPURecordIOTell(void* handle) {
  return ftell(static_cast<RecordReader*>(handle)->f);
}

void MXTPURecordIOReaderFree(void* handle) {
  auto* r = static_cast<RecordReader*>(handle);
  if (r->f) fclose(r->f);
  delete r;
}

// ---- Image pipeline ----
// aug: 10 floats — {random_resized_crop, min_area, max_area, min_aspect,
// max_aspect, brightness, contrast, saturation, hue_deg, inter_method};
// may be null (no augmentation beyond crop/mirror).
void* MXTPUImagePipelineCreate(const char* rec_path,
                               const uint64_t* offsets, uint64_t n,
                               int c, int h, int w, int batch_size,
                               int num_threads, int shuffle, int rand_crop,
                               int rand_mirror, int resize_short,
                               const float* mean, const float* std_,
                               uint64_t seed, const float* aug) {
  auto* p = new ImagePipeline;
  p->f = fopen(rec_path, "rb");
  if (!p->f) {
    delete p;
    return nullptr;
  }
  p->offsets.assign(offsets, offsets + n);
  p->cfg = PipelineConfig{c, h, w, batch_size, num_threads, shuffle,
                          rand_crop, rand_mirror, resize_short,
                          {mean[0], mean[1], mean[2]},
                          {std_[0], std_[1], std_[2]}, seed};
  if (aug != nullptr) {
    p->cfg.random_resized_crop = aug[0] > 0.5f;
    p->cfg.min_area = aug[1];
    p->cfg.max_area = aug[2];
    p->cfg.min_aspect = aug[3];
    p->cfg.max_aspect = aug[4];
    p->cfg.brightness = aug[5];
    p->cfg.contrast = aug[6];
    p->cfg.saturation = aug[7];
    p->cfg.hue_deg = aug[8];
    p->cfg.inter_method = static_cast<int>(aug[9]);
  }
  p->epoch_seed = seed;
  return p;
}

// start (or restart) an epoch
void MXTPUImagePipelineReset(void* handle, uint64_t epoch) {
  auto* p = static_cast<ImagePipeline*>(handle);
  {
    std::lock_guard<std::mutex> lk(p->mu);  // see Shutdown: lost wakeup
    p->stop.store(true);
  }
  p->cv_space.notify_all();
  p->cv_ready.notify_all();
  for (auto& t : p->workers) {
    if (t.joinable()) t.join();
  }
  p->workers.clear();
  {
    std::lock_guard<std::mutex> lk(p->mu);
    for (auto& kv : p->ready) delete kv.second;
    p->ready.clear();
    p->next_emit = 0;
  }
  p->order.resize(p->offsets.size());
  for (size_t i = 0; i < p->order.size(); ++i) p->order[i] = i;
  p->epoch_seed = p->cfg.seed + epoch * 1000003ull;
  if (p->cfg.shuffle) {
    std::mt19937_64 rng(p->epoch_seed);
    std::shuffle(p->order.begin(), p->order.end(), rng);
  }
  p->num_batches = p->order.size() / p->cfg.batch_size;
  p->Start();
}

// copy next batch into out buffers; returns count (0 = epoch done)
int MXTPUImagePipelineNext(void* handle, float* out_data,
                           float* out_labels) {
  auto* p = static_cast<ImagePipeline*>(handle);
  std::unique_lock<std::mutex> lk(p->mu);
  p->cv_ready.wait(lk, [&] {
    return p->ready.count(p->next_emit) ||
           p->active_workers.load() == 0 || p->stop.load();
  });
  auto it = p->ready.find(p->next_emit);
  if (it == p->ready.end()) return 0;
  Batch* b = it->second;
  p->ready.erase(it);
  ++p->next_emit;
  p->cv_space.notify_all();
  lk.unlock();
  std::memcpy(out_data, b->data.data(), b->data.size() * sizeof(float));
  std::memcpy(out_labels, b->labels.data(),
              b->labels.size() * sizeof(float));
  int count = b->count;
  delete b;
  return count;
}

uint64_t MXTPUImagePipelineNumBatches(void* handle) {
  auto* p = static_cast<ImagePipeline*>(handle);
  return p->offsets.size() / p->cfg.batch_size;
}

// records worker `tid` of the decode pool has decoded so far
uint64_t MXTPUImagePipelineDecodedBy(void* handle, int tid) {
  auto* p = static_cast<ImagePipeline*>(handle);
  std::lock_guard<std::mutex> lk(p->mu);
  if (tid < 0 || static_cast<size_t>(tid) >= p->decoded_by.size()) return 0;
  return p->decoded_by[tid];
}

void MXTPUImagePipelineFree(void* handle) {
  delete static_cast<ImagePipeline*>(handle);
}

const char* MXTPUVersion() { return "mxtpu_io 0.1.0"; }

}  // extern "C"
